"""ResNet through the program's normal path, as
examples/resnet50_synthetic_benchmark.py drives it: ParallelMesh(dp=n) ->
training.make_classifier_train_step(resnet.forward, ..., sync_bn) with
optax.sgd.  The benchmark supplies the weights (reference.make_weights)
and reads the state back under the reference's names.
"""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import training
from horovod_tpu.models import resnet
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh


def _program_config(cfg):
    want = (tuple(cfg["stage_blocks"]), cfg["block"] == "bottleneck")
    variants = [v for v, spec in resnet.VARIANTS.items() if spec == want]
    if not variants:
        raise ValueError(f"the program has no ResNet variant for {want}")
    return resnet.ResNetConfig(
        variant=variants[0], num_classes=cfg["num_classes"],
        width=cfg["width"], bn_momentum=cfg["bn_momentum"],
        bn_eps=cfg["bn_eps"], dtype=jnp.dtype(cfg["dtype"]["compute"]))


def _to_program(flat, cfg):
    """The reference's flat names -> the program's params pytree."""
    def bn(name):
        return {"scale": flat[f"{name}.bn.scale"], "bias": flat[f"{name}.bn.bias"]}

    params = {"stem": flat["stem.conv"], "stem_bn": bn("stem"),
              "fc": {"w": flat["fc.w"], "b": flat["fc.b"]}}
    n_convs = 3 if cfg["block"] == "bottleneck" else 2
    for i, n_blocks in enumerate(cfg["stage_blocks"]):
        blocks = []
        for b in range(n_blocks):
            name = f"s{i}.b{b}"
            p = {}
            for k in range(n_convs):
                p[f"conv{k}"] = flat[f"{name}.{k}.conv"]
                p[f"bn{k}"] = bn(f"{name}.{k}")
            if f"{name}.proj.conv" in flat:
                p["proj"] = flat[f"{name}.proj.conv"]
                p["proj_bn"] = bn(f"{name}.proj")
            blocks.append(p)
        params[f"stage{i}"] = blocks
    return params


def _to_flat(params, cfg):
    """The program's params pytree (or one shaped like it) -> flat names."""
    flat = {"stem.conv": params["stem"], "fc.w": params["fc"]["w"],
            "fc.b": params["fc"]["b"]}

    def bn(name, p):
        flat[f"{name}.bn.scale"], flat[f"{name}.bn.bias"] = p["scale"], p["bias"]

    bn("stem", params["stem_bn"])
    for i in range(len(cfg["stage_blocks"])):
        for b, p in enumerate(params[f"stage{i}"]):
            name = f"s{i}.b{b}"
            for key, val in p.items():
                if key.startswith("conv"):
                    flat[f"{name}.{key[4:]}.conv"] = val
                elif key.startswith("bn"):
                    bn(f"{name}.{key[2:]}", val)
                elif key == "proj":
                    flat[f"{name}.proj.conv"] = val
                else:
                    bn(f"{name}.proj", val)
    return flat


class Program:
    """``init(key)`` makes the state on the device from the seed in one
    jitted call; ``step(state, batch)`` is the program's compiled step."""

    def __init__(self, cfg, reference, devices, per_chip_batch):
        self.cfg, self.chips = cfg, len(devices)
        self.global_batch = per_chip_batch * self.chips
        rcfg = _program_config(cfg)
        o = cfg["optimizer"]
        pmesh = ParallelMesh(MeshConfig(dp=self.chips), devices=devices)
        self._ts = training.make_classifier_train_step(
            lambda p, s, x, train, axis_name: resnet.forward(
                p, s, x, rcfg, train=train, axis_name=axis_name),
            lambda rng: resnet.init(rcfg, rng), pmesh,
            optimizer=optax.sgd(o["lr"], momentum=o["momentum"]),
            sync_bn=cfg["sync_bn"])
        self.mesh = self._ts.mesh
        self._data = NamedSharding(self.mesh, self._ts.data_spec)
        replicated = NamedSharding(self.mesh, P())

        def make(k):
            params = _to_program(reference.make_weights(cfg, k), cfg)
            _, stats = resnet.init(rcfg, k)     # structure only: zeros and ones
            return params, stats, optax.sgd(o["lr"], momentum=o["momentum"]).init(params)

        self.init = jax.jit(make, out_shardings=replicated)

    def place(self, samples):
        return tuple(jax.device_put(a, self._data) for a in samples)

    def step(self, state, batch):
        params, stats, opt_state, loss, _ = self._ts.step_fn(*state, *batch)
        return (params, stats, opt_state), loss

    def params(self, state):
        return _to_flat(state[0], self.cfg)

    def first_gradient(self, state):
        """SGD's momentum buffer after one step from zero is the gradient."""
        return _to_flat(state[2][0].trace, self.cfg)

    def compiled(self, state, batch):
        return self._ts.step_fn.lower(*state, *batch).compile()


def build(cfg, reference, devices, per_chip_batch):
    return Program(cfg, reference, devices, per_chip_batch)
