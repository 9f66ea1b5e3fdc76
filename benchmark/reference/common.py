"""What the plain references share: seeded weights, a matrix product whose
precision can be lowered for the control, LayerNorm, AdamW and the readings
that decide ``correct``.  Imports nothing of the program.

Precision modes of :func:`mm` (the only place a product is taken):

- ``"f32"``: float32 operands at ``Precision.HIGHEST`` — the reference.
- ``"bf16"``: operands rounded to bfloat16, float32 accumulation — what the
  configurations state (autocast O1 / bf16 serving); a sanity reading.
- ``"fp8"``: operands scaled per tensor to float8_e4m3fn's range and rounded
  to it, float32 accumulation — the control, the nearest precision below
  bfloat16, the step that would tempt a later PR.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0


def seed_key(seed: int):
    """A key from any whole number up to 2**62 (the driver's seeds pass
    2**31): two 31-bit halves folded together."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              (seed >> 31) % (1 << 31))


def make_weights(spec: dict, seed: int, dtype=jnp.float32) -> dict:
    """All leaves in ONE jitted call on the device.  ``spec`` maps a leaf's
    name to ``(shape, kind)``; kind is a float (std of a normal), ``"ones"``
    or ``"zeros"``."""
    names = sorted(spec)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = spec[name]
            if kind == "ones":
                out[name] = jnp.ones(shape, dtype)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, dtype)
            else:
                out[name] = (float(kind) * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))


def _fp8(x):
    """Per-tensor scaled float8_e4m3fn rounding of the operand; the
    gradient passes straight through (a cotangent cast to unscaled fp8
    would underflow to nought, which no fp8 recipe does)."""
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    low = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(low - x)


def mm(spec: str, a, b, mode: str):
    """``jnp.einsum(spec, a, b)`` in float32 accumulation, operands in the
    precision ``mode`` names."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x * 0.7071067811865476))


def attention(q, k, v, causal: bool, mode: str):
    """q, k, v: [b, s, heads, d] -> [b, s, heads, d]; plain softmax."""
    d = q.shape[-1]
    s = mm("bqhd,bkhd->bhqk", q, k, mode) / np.sqrt(d)
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm("bhqk,bkhd->bqhd", p, v, mode)


def cross_entropy_mean(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - picked)


def split_qkv(cfg: dict, tree: dict) -> dict:
    """The leaves as the comparison sees them: a fused ``*.qkv.w`` /
    ``*.qkv.b`` leaf (columns laid out [heads, (q|k|v), head_dim]) is read as
    three, ``*.q.*``, ``*.k.*``, ``*.v.*``.  A key's bias has no gradient
    under softmax; fused with q and v it would hide inside a leaf that has."""
    heads = cfg["num_attention_heads"]
    out = {}
    for name, x in tree.items():
        if name.endswith(("qkv.w", "qkv.b")):
            base, kind = name[:-5], name[-1]
            parts = jnp.split(x.reshape(x.shape[:-1] + (heads, 3, -1)), 3, -2)
            for tag, part in zip("qkv", parts):
                out[f"{base}{tag}.{kind}"] = part
        else:
            out[name] = x
    return out


def norms(tree: dict) -> dict:
    return {k: jnp.linalg.norm(x.astype(jnp.float32).ravel())
            for k, x in tree.items()}


# ------------------------------------------------------------------ training
class AdamWReference:
    """Follows the first steps of AdamW (decoupled decay on every leaf, as
    ``paddle_tpu.optimizer.AdamW`` defaults) in float32 and takes the
    readings that decide ``correct``: each step's loss, the norm of every
    leaf's first gradient, the norm of every leaf's change after the last
    step, both over the leaves as ``views`` shows them.  ``row_block``
    bounds the rows in flight so the float32 backward fits the chip.  One
    object compiles once and follows any number of seeds."""

    def __init__(self, loss_fn, hyper, views, row_block=None):
        lr, b1, b2 = hyper["learning_rate"], hyper["beta1"], hyper["beta2"]
        eps, wd = hyper["epsilon"], hyper["weight_decay"]

        def loss_and_grads(params, batch):
            """Mean loss and its gradient, block of rows by block of rows
            (equal blocks, so the mean of block means is the mean)."""
            n = batch[0].shape[0]
            blk = row_block if row_block and n % row_block == 0 else n
            parts = [x.reshape((n // blk, blk) + x.shape[1:]) for x in batch]

            def body(acc, part):
                l, g = jax.value_and_grad(loss_fn)(params, *part)
                scale = blk / n
                return (acc[0] + l * scale, jax.tree_util.tree_map(
                    lambda a, b: a + b * scale, acc[1], g)), None

            zero = (jnp.zeros((), jnp.float32),
                    jax.tree_util.tree_map(jnp.zeros_like, params))
            return jax.lax.scan(body, zero, parts)[0]

        @jax.jit
        def step(params, m, v, t, *batch):
            loss, grads = loss_and_grads(params, batch)
            gnorm = norms(views(grads))
            new_p, new_m, new_v = {}, {}, {}
            for k, p in params.items():
                g = grads[k]
                new_m[k] = b1 * m[k] + (1 - b1) * g
                new_v[k] = b2 * v[k] + (1 - b2) * g * g
                mhat = new_m[k] / (1 - b1 ** t)
                vhat = new_v[k] / (1 - b2 ** t)
                new_p[k] = (p * (1.0 - lr * wd)
                            - lr * mhat / (jnp.sqrt(vhat) + eps))
            return loss, gnorm, new_p, new_m, new_v

        @jax.jit
        def change(new, old):
            return norms(views({k: new[k] - old[k] for k in new}))

        self._step, self._change = step, change

    def follow(self, weights, batches, rows=None):
        """``rows`` keeps only the first rows of each batch (the half-batch
        fault, planted in the reference)."""
        params = weights
        m = jax.tree_util.tree_map(jnp.zeros_like, weights)
        v = jax.tree_util.tree_map(jnp.zeros_like, weights)
        losses, first_gnorm = [], None
        for t, batch in enumerate(batches, start=1):
            batch = [jnp.asarray(x[:rows] if rows else x) for x in batch]
            loss, gnorm, params, m, v = self._step(params, m, v, float(t),
                                                   *batch)
            losses.append(float(loss))
            if first_gnorm is None:
                first_gnorm = {k: float(x) for k, x in gnorm.items()}
        dnorm = {k: float(x)
                 for k, x in self._change(params, weights).items()}
        return {"loss": losses, "grad_norm": first_gnorm,
                "change_norm": dnorm}
