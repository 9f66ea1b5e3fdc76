"""Mixture-of-Experts with expert parallelism — TPU-native.

Reference parity:
  python/paddle/incubate/distributed/models/moe/moe_layer.py (MoELayer),
  .../moe/gate/{naive_gate,gshard_gate,switch_gate}.py,
  python/paddle/distributed/utils/moe_utils.py (global_scatter/global_gather).

The reference is FastMoE-style: data-dependent scatter of tokens into
per-expert buffers, NCCL all-to-all of ragged counts, per-expert Linear
loops.  None of that maps to XLA: data-dependent shapes don't compile, and
ragged buffers defeat the MXU.  The TPU-native design is the GShard/Switch
formulation: every routing decision becomes a STATIC-shape one-hot
``dispatch`` mask [tokens, experts, capacity] and a differentiable
``combine`` tensor of gate weights; dispatch/combine are einsums (MXU
work), tokens over capacity are dropped (the residual connection carries
them), and expert parallelism is a sharding annotation on the expert axis
of the [E, C, d] dispatched activations — XLA's partitioner inserts the
same all-to-all the reference issues by hand through NCCL.

:class:`DroplessMoELayer` is the other formulation, for routers whose
load may not be capped (DeepSeek-V3's sigmoid router): token-expert pairs
are sorted by expert and every expert held runs over its own contiguous
rows in ONE grouped product (work proportional to ``tokens x top_k``), so
no token is dropped at any load and no ``[n, E, C]`` tensor exists.  The
product is ``ops/pallas/grouped_matmul.py``'s: the Pallas kernel where
``pick_tiles`` names it (on a TPU, over the rows an expert its sweep
measured), ``jax.lax.ragged_dot`` elsewhere.
"""
from __future__ import annotations

import contextlib
import contextvars
import math

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.distributed.fleet.meta_parallel import _constrain
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I

__all__ = [
    "BaseGate", "NaiveGate", "GShardGate", "SwitchGate",
    "MoELayer", "StackedExpertFFN", "dispatch_combine",
    "DroplessMoELayer", "dropless_experts", "sigmoid_topk_route",
    "softmax_topk_route", "grouped_tally", "experts_path",
]


def _capacity(num_tokens, num_experts, top_k, capacity_factor):
    """GShard per-expert capacity: each expert can take its fair share of
    the top_k routed tokens, scaled by the capacity factor."""
    return max(1, math.ceil(capacity_factor * num_tokens * top_k
                            / num_experts))


def dispatch_combine(probs, top_k, capacity, keep_last=None):
    """Static-shape GShard routing tensors from router probabilities.

    probs: [n, E] router probabilities (post-softmax, differentiable).
    keep_last: optional [n] 0/1 mask gating each token's LAST (lowest-
    priority) expert choice — the hook for GShard's stochastic
    second-expert routing.
    Returns (combine [n, E, C], dispatch [n, E, C]) where dispatch is the
    0/1 routing mask (top_k choices, position-in-expert < capacity, GShard
    priority: all top-1 picks claim capacity before any top-2 pick) and
    combine carries the gate weights at the same positions.  Both are
    differentiable in `probs` through the top-k gate values.
    """
    def fn(p, *rest):
        return gshard_dispatch_combine(p, top_k, capacity,
                                       rest[0] if rest else None)

    if keep_last is not None:
        return apply(fn, probs, keep_last)
    return apply(fn, probs)


def gshard_dispatch_combine(p, top_k, capacity, kl=None):
    """Plain-jnp GShard routing core shared by the nn MoELayer and the
    explicit hybrid (models/gpt_hybrid._moe_ffn). p: [n, E] probs."""
    import jax
    import jax.numpy as jnp

    n, e = p.shape
    vals, idx = jax.lax.top_k(p, top_k)            # [n, K]
    onehot = jax.nn.one_hot(idx, e, dtype=p.dtype)  # [n, K, E]
    if kl is not None:
        onehot = onehot.at[:, top_k - 1, :].multiply(
            kl.astype(p.dtype)[:, None])
    # rank of each token within its chosen expert; top-1 column fills
    # before top-2 (GShard §3.2) so the primary route wins capacity
    offset = jnp.zeros((e,), p.dtype)
    keep_k, pos_k = [], []
    for k in range(top_k):
        mk = onehot[:, k, :]                        # [n, E]
        pos = jnp.cumsum(mk, axis=0) - mk + offset  # [n, E]
        offset = offset + mk.sum(axis=0)
        keep_k.append(mk * (pos < capacity))
        pos_k.append(pos)
    keep = jnp.stack(keep_k, 1)                     # [n, K, E]
    pos = jnp.stack(pos_k, 1)                       # [n, K, E]
    slot = jax.nn.one_hot(
        jnp.clip(pos, 0, capacity - 1).astype(jnp.int32), capacity,
        dtype=p.dtype)                              # [n, K, E, C]
    disp_k = keep[..., None] * slot                 # [n, K, E, C]
    dispatch = disp_k.sum(axis=1)
    combine = (vals[:, :, None, None] * disp_k).sum(axis=1)
    return combine, dispatch


class BaseGate(nn.Layer):
    """Reference-API base: gates stash their auxiliary (load-balancing)
    loss; the training loop reads it via get_loss() and adds it to the
    task loss (reference moe/gate/base_gate.py)."""

    def __init__(self, num_expert, world_size=1):
        super().__init__()
        self.world_size = world_size
        self.num_expert = num_expert
        self.tot_expert = num_expert * world_size
        self.loss = None

    def set_loss(self, loss):
        self.loss = loss

    def get_loss(self, clear=True):
        loss = self.loss
        if clear:
            self.loss = None
        return loss


class NaiveGate(BaseGate):
    """Linear router + top-k softmax over the selected experts
    (reference moe/gate/naive_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2):
        super().__init__(num_expert, world_size)
        self.top_k = topk
        self.gate = nn.Linear(
            d_model, self.tot_expert,
            weight_attr=I.ParamAttr(initializer=I.Normal(0.0, 0.02)))

    def scores(self, x):
        """Full softmax router probabilities [n, E] (differentiable)."""
        return F.softmax(self.gate(x), axis=-1)

    def forward(self, x, return_all_scores=False):
        logits = self.gate(x)
        vals, idx = paddle_tpu.topk(logits, self.top_k, axis=-1)
        vals = F.softmax(vals, axis=-1)
        if return_all_scores:
            return vals, idx, logits
        return vals, idx


class GShardGate(NaiveGate):
    """Top-2 gate with the GShard load-balancing auxiliary loss
    mean(c_e * m_e) * E^2 (reference moe/gate/gshard_gate.py) and optional
    stochastic second-expert routing."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2,
                 capacity=(1.2, 2.4), random_routing=True, group=None):
        assert topk == 2, "topk should be 2 in gshard"
        super().__init__(d_model, num_expert, world_size, topk=topk)
        self.capacity_factor = capacity
        self.random_routing = random_routing

    def aux_loss(self, probs, top1_idx):
        c_e = F.one_hot(top1_idx, self.tot_expert).mean(axis=0)
        m_e = probs.mean(axis=0)
        loss = (c_e * m_e).mean() * (self.tot_expert ** 2)
        self.set_loss(loss)
        return loss


class SwitchGate(NaiveGate):
    """Top-1 gate with multiplicative jitter noise in training and the
    Switch-Transformer balance loss sum(f_e * p_e) * E
    (reference moe/gate/switch_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=1,
                 switch_eps=0.1, capacity=(1.2, 2.4), group=None):
        assert topk == 1, "topk should be 1 in switch"
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.switch_eps = switch_eps
        self.capacity_factor = capacity

    def scores(self, x):
        logits = self.gate(x)
        if self.training and self.switch_eps:
            noise = paddle_tpu.rand(logits.shape, dtype="float32")
            logits = logits * (
                noise * (2 * self.switch_eps) + (1.0 - self.switch_eps))
        return F.softmax(logits, axis=-1)

    def aux_loss(self, probs, top1_idx):
        f_e = F.one_hot(top1_idx, self.tot_expert).mean(axis=0)
        p_e = probs.mean(axis=0)
        loss = (f_e * p_e).sum() * self.tot_expert
        self.set_loss(loss)
        return loss


class StackedExpertFFN(nn.Layer):
    """All experts' FFN weights stacked on a leading expert axis so the
    expert compute is ONE batched einsum over [E, C, d] — the MXU-friendly
    replacement for the reference's Python loop over per-expert Linears.
    Weights are annotated to shard over the `ep` mesh axis."""

    def __init__(self, num_experts, d_model, d_hidden, ep_axis="ep",
                 activation="gelu"):
        super().__init__()
        from paddle_tpu.distributed.mesh import shard_tensor
        self.num_experts = num_experts
        self.ep_axis = ep_axis
        self.activation = activation
        init = I.Normal(0.0, 0.02)
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden],
                                        default_initializer=init)
        self.b1 = self.create_parameter(
            [num_experts, d_hidden], default_initializer=I.Constant(0.0))
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model],
                                        default_initializer=init)
        self.b2 = self.create_parameter(
            [num_experts, d_model], default_initializer=I.Constant(0.0))
        for w in (self.w1, self.b1, self.w2, self.b2):
            shard_tensor(w, ep_axis)

    def forward(self, x):
        # x: [E, C, d] dispatched tokens, expert axis sharded over ep
        h = paddle_tpu.einsum("ecd,edh->ech", x, self.w1) + self.b1.unsqueeze(1)
        h = F.gelu(h, approximate=True) if self.activation == "gelu" \
            else F.relu(h)
        return paddle_tpu.einsum("ech,ehd->ecd", h, self.w2) \
            + self.b2.unsqueeze(1)


class MoELayer(nn.Layer):
    """Mixture-of-experts layer (reference moe_layer.py MoELayer).

    Args mirror the reference: `experts` is either a LayerList of
    per-expert Layers ([C, d] -> [C, d]) or a StackedExpertFFN; `gate` a
    dict config ({"type": "gshard"|"switch"|"naive", "top_k": k}) or a
    BaseGate instance.  `moe_group`/`mp_group` become the `ep_axis` mesh
    axis name — the reference's process groups are mesh axes here, and the
    all-to-all the reference issues through NCCL (global_scatter /
    global_gather) is inserted by the XLA partitioner from the sharding
    constraint on the dispatched [E, C, d] activations.

    Tokens routed beyond an expert's capacity contribute zero output (the
    surrounding residual carries them) — identical semantics to the
    reference's capacity-limited gates.
    """

    def __init__(self, d_model, experts, gate=None, moe_group=None,
                 mp_group=None, ep_axis="ep", capacity_factor=(1.2, 2.4),
                 recompute_interval=0, recompute_ctx=None):
        super().__init__()
        self.d_model = d_model
        self.ep_axis = ep_axis if moe_group is None else moe_group
        if isinstance(experts, StackedExpertFFN):
            self.experts = experts
            self.num_expert = experts.num_experts
        else:
            self.experts = nn.LayerList(list(experts))
            self.num_expert = len(self.experts)

        if gate is None or isinstance(gate, dict):
            gate = dict(gate or {})
            top_k = gate.get("top_k", 2)
            kind = gate.get("type", "gshard")
            if kind in (None, "naive"):
                gate = NaiveGate(d_model, self.num_expert, topk=top_k)
            elif kind == "gshard":
                gate = GShardGate(d_model, self.num_expert, topk=top_k,
                                  capacity=capacity_factor)
            elif kind == "switch":
                gate = SwitchGate(d_model, self.num_expert,
                                  capacity=capacity_factor)
            else:
                raise ValueError(f"unknown gate type {kind!r}")
        elif not isinstance(gate, BaseGate):
            raise TypeError("gate must be a dict config or a BaseGate")
        self.gate = gate
        self.top_k = gate.top_k
        self.capacity_factor = getattr(gate, "capacity_factor",
                                       capacity_factor)

    def _run_experts(self, xin):
        if isinstance(self.experts, StackedExpertFFN):
            return self.experts(xin)
        outs = [self.experts[e](xin[e]) for e in range(self.num_expert)]
        return paddle_tpu.stack(outs, axis=0)

    def forward(self, x):
        orig_shape = x.shape
        n = 1
        for s in orig_shape[:-1]:
            n *= s
        xf = x.reshape([n, self.d_model])

        probs = self.gate.scores(xf)                       # [n, E]
        _, top_idx = paddle_tpu.topk(probs, self.top_k, axis=-1)
        if hasattr(self.gate, "aux_loss"):
            self.gate.aux_loss(probs, top_idx[:, 0])

        cap_rate = self.capacity_factor[0 if self.training else 1]
        capacity = _capacity(n, self.num_expert, self.top_k, cap_rate)
        # GShard stochastic second-expert routing (reference
        # moe/utils.py _random_routing): keep the 2nd choice with
        # probability min(1, 2 * its gate value)
        keep_last = None
        if (self.training and self.top_k == 2
                and getattr(self.gate, "random_routing", False)):
            vals2, _ = paddle_tpu.topk(probs, 2, axis=-1)
            r = paddle_tpu.rand([n], dtype="float32")
            keep_last = (vals2[:, 1] * 2.0 > r).astype("float32")
        combine, dispatch = dispatch_combine(probs, self.top_k, capacity,
                                             keep_last=keep_last)

        xin = paddle_tpu.einsum("nec,nd->ecd", dispatch, xf)
        xin = _constrain(xin, self.ep_axis, None, None)
        out = self._run_experts(xin)                       # [E, C, d]
        out = _constrain(out, self.ep_axis, None, None)
        y = paddle_tpu.einsum("nec,ecd->nd", combine, out)
        return y.reshape(orig_shape)


# ------------------------------------------------------------- dropless
def sigmoid_topk_route(h, gate_w, gate_bias, top_k, scale,
                       norm_topk_prob=True):
    """DeepSeek-V3's router (``scoring_func: sigmoid``, ``topk_method:
    noaux_tc`` with one group) on tokens ``h [n, d]``: scores in float32
    whatever ``h`` is stored in, the choice made on ``score + gate_bias``
    (``e_score_correction_bias``), the weights from the plain scores of
    the chosen, normalised over them and scaled.  Returns (weights
    ``[n, k]`` f32, experts ``[n, k]`` int32)."""
    import jax
    import jax.numpy as jnp
    g = jnp.matmul(h.astype(jnp.float32), gate_w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    sc = jax.nn.sigmoid(g)
    _, idx = jax.lax.top_k(sc + gate_bias.astype(jnp.float32), top_k)
    top = jnp.take_along_axis(sc, idx, -1)
    if norm_topk_prob:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return top * scale, idx.astype(jnp.int32)


def softmax_topk_route(h, gate_w, top_k):
    """Granite's router (``granitemoehybrid``, as Mixtral's) on tokens ``h
    [n, d]``: the logits in float32 whatever ``h`` is stored in, the top
    ``top_k`` of the LOGITS, the weights a softmax over the chosen alone.
    Returns (weights ``[n, k]`` f32, experts ``[n, k]`` int32)."""
    import jax
    import jax.numpy as jnp
    g = jnp.matmul(h.astype(jnp.float32), gate_w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(g, top_k)
    return jax.nn.softmax(top, axis=-1), idx.astype(jnp.int32)


_GROUPED_TALLY = contextvars.ContextVar("grouped_tally", default=None)


@contextlib.contextmanager
def grouped_tally():
    """Collects, while a program is traced, one entry a grouped product of
    :func:`dropless_experts`: True where it took the Pallas kernel, False
    where ``ragged_dot``.  Yields the list."""
    took = []
    token = _GROUPED_TALLY.set(took)
    try:
        yield took
    finally:
        _GROUPED_TALLY.reset(token)


def experts_path():
    """What the grouped expert products are built from, for the serving
    AOT fingerprint: the kernel at its revision where a kernel may run
    (which products take it is ``pick_tiles``' rule of the shapes, which
    the fingerprint covers), else ``ragged_dot``."""
    from paddle_tpu.ops.pallas import kernel_default
    from paddle_tpu.ops.pallas.grouped_matmul import GROUPED_MATMUL_REVISION
    return (f"grouped_matmul/{GROUPED_MATMUL_REVISION}" if kernel_default()
            else "ragged_dot")


def _grouped(rows, w, counts):
    """One grouped product ``rows [m, K]`` x ``w [G, K, N]`` -> f32, on the
    path ``pick_tiles`` names for its shape, noted in the tally."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    tiles = gm.pick_tiles(rows.shape[0], w.shape[0], w.shape[1], w.shape[2],
                          rows.dtype)
    took = _GROUPED_TALLY.get()
    if took is not None:
        took.append(tiles is not None)
    return gm.grouped_matmul(rows, w, counts, tiles)


def dropless_experts(h, weights, idx, w13, w2, first=0, share=False,
                     act="silu"):
    """Routed experts without a capacity: ``sum_i weights[:, i] *
    E_idx[:, i](h)`` over the experts HELD, ``E(h) = (act(h W1) * (h
    W3)) W2`` — ``act`` ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU).

    ``h [n, d]``; ``weights`` / ``idx [n, k]`` from a router over ALL
    experts; ``w13 [E_held, d, 2f]`` (``[W1 | W3]``) and ``w2 [E_held, f,
    d]`` the stacked weights of experts ``first .. first + E_held - 1``.
    A pair whose expert is held elsewhere adds nothing here (its share of
    the result is another holder's).  The ``n * k`` pairs are sorted by
    expert, so each expert's rows are contiguous and the two grouped
    products (:func:`_grouped`) do ``n * k`` rows of work; rows come back
    to their tokens by the inverse permutation (a gather).  ``share``: the
    experts held are a share of the router's, so some pairs' rows lie past
    the last group, where the grouped product writes NOTHING (on a TPU
    they hold whatever the memory held, NaN included, and a weight of 0
    would not clear that): those rows are set to 0.  Returns (out ``[n,
    d]`` in ``h``'s dtype, tokens per held expert ``[E_held]`` int32)."""
    import jax
    import jax.numpy as jnp
    n, k = idx.shape
    held = w13.shape[0]
    local = idx.reshape(-1) - first
    mine = (local >= 0) & (local < held)
    local = jnp.where(mine, local, held)        # others sort to the end
    order = jnp.argsort(local, stable=True)
    counts = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    rows = h[order // k]                                     # [n*k, d]
    a = _grouped(rows, w13, counts)
    gate, up = jnp.split(a, 2, -1)
    gate = jax.nn.relu(gate) if act == "relu" else jax.nn.silu(gate)
    y = _grouped((gate * up).astype(h.dtype), w2, counts)
    # rows past the held experts' are not this holder's: weight 0
    wts = jnp.where(mine, weights.reshape(-1), 0.0)[order]
    if share:
        y = jnp.where(mine[order][:, None], y, 0.0)
    y = y * wts[:, None]
    back = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    return y[back].reshape(n, k, -1).sum(1).astype(h.dtype), counts


class DroplessMoELayer(nn.Layer):
    """Routed experts with shared experts, nothing dropped.  ``route``
    is the model's declaration of its router: ``"sigmoid"`` (DeepSeek-V3 /
    Kanana-2, :func:`sigmoid_topk_route`, with the selection bias
    ``gate_bias``; equations in docs/serving.md "Latent pool and dropless
    experts") or ``"softmax"`` (Granite, :func:`softmax_topk_route`: no
    bias, no scaling).  ``act`` is the gate's activation of every routed
    expert: ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU, SmallThinker's).

    The layer is told which contiguous range of experts it holds
    (``held = (first, count)``, default all): it routes over all
    ``num_experts`` and computes the held experts' part of the result —
    what expert parallelism asks of each holder; the shared experts are
    every holder's alike (``shared=False`` leaves them to one holder).
    Stacked leaves: ``w13 [count, d, 2f]``, ``w2 [count, f, d]``.

    ``forward(x, route_from=None)`` returns the layer's output; the
    router reads ``route_from`` where it is given (a model whose router
    sits before another block, as SmallThinker's before attention) and
    ``x`` otherwise, the experts always read ``x``.  The tokens each held
    expert got in that call are kept in ``last_counts`` (a traced
    ``[count]`` int32 inside a compiled program) for whoever counts the
    routing.
    """

    def __init__(self, d_model, d_expert, num_experts, top_k, n_shared=0,
                 routed_scaling_factor=1.0, norm_topk_prob=True, held=None,
                 shared=True, initializer_range=0.02, make_parameter=None,
                 route="sigmoid", act="silu"):
        super().__init__()
        if route not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown router {route!r}")
        if act not in ("silu", "relu"):
            raise ValueError(f"unknown expert activation {act!r}")
        self.act = act
        self.d_model, self.num_experts, self.top_k = d_model, num_experts, top_k
        self.scale, self.norm_topk_prob = routed_scaling_factor, norm_topk_prob
        self.route = route
        self.first, count = held if held is not None else (0, num_experts)
        self.share = count != num_experts
        self.last_counts = None
        make = make_parameter or (
            lambda shape, std: self.create_parameter(
                shape, default_initializer=(
                    I.Normal(0.0, std) if std else I.Constant(0.0))))
        std = initializer_range
        self.gate_weight = make([d_model, num_experts], std)
        # e_score_correction_bias: fitted while training, loaded with the
        # weights; it moves the choice and never enters a weight
        if route == "sigmoid":
            self.gate_bias = make([num_experts], 0.0)
        self.w13 = make([count, d_model, 2 * d_expert], std)
        self.w2 = make([count, d_expert, d_model], std)
        self.has_shared = bool(shared and n_shared)
        if self.has_shared:
            self.shared_w13 = make([d_model, 2 * n_shared * d_expert], std)
            self.shared_w2 = make([n_shared * d_expert, d_model], std)

    def forward(self, x, route_from=None):
        import jax
        import jax.numpy as jnp

        if self.route == "sigmoid":
            router = (self.gate_weight, self.gate_bias)

            def route(h, gw, gb):
                return sigmoid_topk_route(h, gw, gb, self.top_k, self.scale,
                                          self.norm_topk_prob)
        else:
            router = (self.gate_weight,)

            def route(h, gw):
                return softmax_topk_route(h, gw, self.top_k)

        source = () if route_from is None else (route_from,)

        def fn(v, *leaves):
            h = v.reshape(-1, v.shape[-1])
            r = h
            if source:
                r, *leaves = leaves
                r = r.reshape(-1, r.shape[-1])
            weights, idx = route(r, *leaves[:len(router)])
            w13, w2, *shared = leaves[len(router):]
            out, counts = dropless_experts(h, weights, idx, w13, w2,
                                           self.first, self.share, self.act)
            if shared:
                a = jnp.matmul(h, shared[0],
                               preferred_element_type=jnp.float32)
                gate, up = jnp.split(a, 2, -1)
                out = out + jnp.matmul(
                    (jax.nn.silu(gate) * up).astype(h.dtype), shared[1],
                    preferred_element_type=jnp.float32).astype(h.dtype)
            return out.reshape(v.shape), counts

        shared = ((self.shared_w13, self.shared_w2) if self.has_shared
                  else ())
        out, counts = apply(fn, x, *source, *router, self.w13, self.w2,
                            *shared)
        self.last_counts = counts
        return out
