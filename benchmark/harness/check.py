"""What decides ``correct``: the timed step's first three steps against
the configuration's plain float32 reference, which follows the same three
steps with a plain optimizer of its own.

Numbers compared (each printed beside its limit in every run):

  loss_gap           worst of the three steps' |loss - ref| / |ref|
  grad_norm_gap      worst leaf of the first gradient as the optimizer got it
  grad_norm_mid_gap  the median leaf of the same: a widest gap swings from
                     seed to seed by its nature, the median leaf does not
  grad_share_gap     worst leaf of each leaf's share of the whole gradient's
                     norm: blind to an error common to every leaf (bf16 at
                     32 [CLS] rows puts up to 1% on all of BERT's at once),
                     so what is left is each layer's own arithmetic
  update_norm_gap    worst leaf of the parameters' change after three steps

A leaf's gap is | ||program|| - ||reference|| | over the larger of the
reference's norm of that leaf and of the median leaf.  A leaf whose
reference gradient is under a millionth of the median leaf's has no
gradient but rounding (BERT's key bias: softmax ignores it); Adam scales
that rounding to a full-size update, so such a leaf's change is not
compared.
"""

import statistics

import jax
import jax.numpy as jnp
from jax import lax

STEPS = 3
_FP8_MAX = 448.0


def leaf_norms(flat):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


def quant_fp8(a):
    """The control's arithmetic: operands rounded to fp8 (e4m3) under a
    per-tensor scale, gradients passed straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return a + lax.stop_gradient(q - a)


def _sgd(o, w, g, opt, t):
    trace = {k: g[k] + o["momentum"] * opt[k] for k in w}
    return {k: w[k] - o["lr"] * trace[k] for k in w}, trace


def _adamw(o, w, g, opt, t):
    mu = {k: o["b1"] * opt[0][k] + (1 - o["b1"]) * g[k] for k in w}
    nu = {k: o["b2"] * opt[1][k] + (1 - o["b2"]) * g[k] ** 2 for k in w}
    new = {}
    for k in w:
        m_hat = mu[k] / (1 - o["b1"] ** t)
        v_hat = nu[k] / (1 - o["b2"] ** t)
        new[k] = w[k] - o["lr"] * (m_hat / (jnp.sqrt(v_hat) + o["eps"])
                                   + o["weight_decay"] * w[k])
    return new, (mu, nu)


_OPTIMIZERS = {"sgd": (_sgd, lambda w: jax.tree_util.tree_map(jnp.zeros_like, w)),
               "adamw": (_adamw, lambda w: (jax.tree_util.tree_map(jnp.zeros_like, w),) * 2)}


class Reference:
    """The configuration's plain reference, ready to follow a seed's first
    three steps with a plain optimizer of its own: built once, run per
    seed.  ``quant`` puts the control's arithmetic in its matrix products.

    The arithmetic is that of one device over the whole batch.  Given
    several ``devices`` the same plain code is handed its rows spread over
    them and the compiler partitions it (the float32 activations of a
    four-chip batch do not fit one chip); nothing in it names a device.
    """

    def __init__(self, reference, cfg, devices=None, quant=None):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(devices if devices is not None else jax.devices()[:1],
                    ("rows",))
        self._rows = NamedSharding(mesh, P("rows"))
        update, init = _OPTIMIZERS[cfg["optimizer"]["kind"]]
        kwargs = {} if quant is None else {"quant": quant}

        def step(w, opt, batch, t):
            with jax.default_matmul_precision("highest"):
                loss, g = jax.value_and_grad(
                    lambda w_: reference.loss(cfg, w_, batch, **kwargs))(w)
                w, opt = update(cfg["optimizer"], w, g, opt, t)
            return w, opt, loss, leaf_norms(g)

        replicated = NamedSharding(mesh, P())
        self._weights = jax.jit(lambda key: reference.make_weights(cfg, key),
                                out_shardings=replicated)
        self._init = jax.jit(init, out_shardings=replicated)
        self._step = jax.jit(step, donate_argnums=(0, 1))
        self._delta = jax.jit(lambda a, b: leaf_norms({k: a[k] - b[k] for k in a}))

    def _start(self, key):
        w = self._weights(key)
        return w, self._init(w)

    def run(self, weights_key, batches):
        """Python floats: {"losses": [..], "grad_norms": {leaf: n},
        "update_norms": {leaf: n}}."""
        w, opt = self._start(weights_key)
        losses, grad_norms = [], None
        for t, batch in enumerate(batches[:STEPS], start=1):
            batch = tuple(jax.device_put(a, self._rows) for a in batch)
            w, opt, loss, g_norms = self._step(w, opt, batch, jnp.float32(t))
            losses.append(loss)
            grad_norms = grad_norms or g_norms
        # The first weights are made again from the key for the difference,
        # as run.py does for the program: a copy kept through the steps
        # would be 4 of 20 bytes a parameter beside the float32 activations.
        # By the program that made them, not inside ``_delta``: there they
        # fuse into the sums and the norms' last bits move.
        out = jax.device_get({
            "losses": losses, "grad_norms": grad_norms,
            "update_norms": self._delta(w, self._weights(weights_key))})
        return jax.tree_util.tree_map(float, out)


NO_GRADIENT = 1e-6


def _leaf_gaps(got, ref, keep=None):
    floor = statistics.median(ref.values())
    gaps = {}
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        gap = abs(float(got[k]) - float(r)) / max(float(r), floor, 1e-30)
        gaps[k] = gap if gap == gap else float("inf")   # a NaN is the worst
    return gaps


def _shares(norms):
    total = sum(float(v) ** 2 for v in norms.values()) ** 0.5
    return {k: float(v) / max(total, 1e-30) for k, v in norms.items()}


def compare(got, ref):
    """{number: (value, leaf or step it was read at)}."""
    loss_gaps = [abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)
                 for a, b in zip(got["losses"], ref["losses"])]
    loss_gaps = [g if g == g else float("inf") for g in loss_gaps]
    worst_step = max(range(len(loss_gaps)), key=loss_gaps.__getitem__)
    grad = _leaf_gaps(got["grad_norms"], ref["grad_norms"])
    share = _leaf_gaps(_shares(got["grad_norms"]), _shares(ref["grad_norms"]))
    mid = statistics.median(ref["grad_norms"].values())
    has_gradient = {k for k, v in ref["grad_norms"].items()
                    if v >= NO_GRADIENT * mid}
    update = _leaf_gaps(got["update_norms"], ref["update_norms"], has_gradient)
    worst_g, worst_u = max(grad, key=grad.get), max(update, key=update.get)
    worst_s = max(share, key=share.get)
    return {
        "loss_gap": (loss_gaps[worst_step], f"step {worst_step + 1}"),
        "grad_norm_gap": (grad[worst_g], worst_g),
        "grad_norm_mid_gap": (statistics.median(grad.values()), "median leaf"),
        "grad_share_gap": (share[worst_s], worst_s),
        "update_norm_gap": (update[worst_u], worst_u),
    }


def within(numbers, limits):
    return all(numbers[name][0] <= limit for name, limit in limits.items())
