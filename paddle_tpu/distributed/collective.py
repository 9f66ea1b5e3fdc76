"""Collective ops. Reference: python/paddle/distributed/collective.py.

The reference's c_allreduce/c_broadcast/... ops dispatch NCCL kernels; here
each collective is an XLA collective on a mesh axis:
  - inside a shard_map body (collective_axis set): lax.psum / all_gather /
    ppermute / all_to_all — compiled onto ICI.
  - eager multi-host (jax.distributed): multihost_utils fallbacks over DCN.
  - single process, no axis: identity (world of one).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import apply
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import mesh as dmesh


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A mesh-axis-backed communication group."""

    def __init__(self, axis=None, ranks=None, id=0):
        self.axis = axis
        self.ranks = ranks or []
        self.id = id

    @property
    def nranks(self):
        if self.axis is not None:
            return dmesh.axis_size(self.axis)
        return get_world_size()

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return rank

    @property
    def rank(self):
        return get_rank()


_default_group = Group()


def new_group(ranks=None, backend=None, axis=None):
    return Group(axis=axis, ranks=ranks, id=1)


def get_group(gid=0):
    return _default_group


def _axis_of(group):
    if group is not None and getattr(group, "axis", None):
        return group.axis
    return dmesh.current_collective_axis()


def get_rank(group=None):
    axis = _axis_of(group)
    if axis is not None:
        # Inside a shard_map body this is a per-shard traced value — return
        # it as-is so rank-dependent code computes with the true rank on each
        # shard (an int() here would silently collapse every shard to rank 0).
        return jax.lax.axis_index(axis)
    # eager path: the FLEET rank — contiguous within the survivor set
    # after an elastic reconfigure (== jax.process_index() at launch)
    from paddle_tpu.resilience import fleet
    return fleet.world().rank


def get_world_size(group=None):
    axis = _axis_of(group)
    if axis is not None:
        return dmesh.axis_size(axis)
    from paddle_tpu.resilience import fleet
    return fleet.world().size


# monotone per-process round counter for coordination-service
# collectives; SPMD call order is identical on every process, so the
# same round id names the same collective fleet-wide.  Keys are
# namespaced by the fleet launch id + generation (fleet.coord_namespace)
# so an aborted run's debris can't collide with the next, and a clean
# exit / reconfigure reaps the whole namespace in one delete.
# _COORD_REAPED tracks the newest round PROVEN globally complete and
# already swept (see _coord_reap for the proof obligation).
_COORD_ROUND = [0]
_COORD_REAPED = [0]
_REAP_BATCH = 64     # max rounds swept per allgather (no delete storms)


def reset_coord_rounds():
    """Fresh round counters for a fresh key namespace — called by
    ``resilience.fleet.reconfigure`` after the generation bump (every
    survivor resets identically; the new namespace guarantees no
    collision with in-flight old-generation keys)."""
    _COORD_ROUND[0] = 0
    _COORD_REAPED[0] = 0


def _coord_client():
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise RuntimeError(
            "jax.distributed is not initialized — multi-process "
            "collectives need distributed.launch / "
            "jax.distributed.initialize first")
    return client


def _coord_get(client, key, missing_rank, rnd):
    """One peer contribution, timeout-bounded (fleet.kv_get_bytes):
    sliced blocking gets under the configured deadline, aborting early
    the moment the fleet watchdog holds a DEAD verdict for the awaited
    rank — raises ``CollectiveTimeout`` naming it, never hangs."""
    from paddle_tpu.resilience import fleet

    mon = fleet.get_monitor()
    abort_if = (None if mon is None
                else (lambda: mon.is_dead(missing_rank)))
    return fleet.kv_get_bytes(
        client, key, fleet.get_config().collective_timeout_s,
        site="fleet.kv_get", missing_rank=missing_rank,
        abort_if=abort_if, seed=rnd)


def _coord_allgather(value):
    """Eager cross-process allgather over the jax.distributed
    coordination service's key-value store (the same coordinator
    ``launch()`` / ``jax.distributed.initialize`` stood up).

    XLA:CPU cannot execute multi-process SPMD programs, so the
    ``multihost_utils`` path is TPU/GPU-only; this DCN fallback keeps
    the eager collective API working in multi-process CPU worlds
    (tests/test_distributed_multiprocess.py proves it end to end).
    Stacks every member's array along a new leading axis, in fleet
    member order — after an elastic reconfigure the world is the
    survivor set, not ``jax.process_count()``."""
    import pickle

    import numpy as np

    from paddle_tpu.resilience import fleet

    client = _coord_client()
    wv = fleet.world()
    _COORD_ROUND[0] += 1
    rnd = _COORD_ROUND[0]
    prefix = f"{fleet.coord_namespace()}/allgather/{rnd}"
    arr = np.asarray(value)
    fleet.kv_set_bytes(client, f"{prefix}/{wv.global_rank}",
                       pickle.dumps(arr))
    parts = []
    for r in wv.members:
        raw = _coord_get(client, f"{prefix}/{r}", r, rnd)
        parts.append(pickle.loads(raw))
    _coord_reap(client, wv.rank, rnd)
    return np.stack(parts)


def _coord_reap(client, rank, rnd):
    """Reap all rounds strictly BEFORE `rnd`, from inside an allgather
    whose every member key has just been received.  That receipt is
    the proof that makes the sweep safe: each member publishes its
    round-`rnd` key on ENTERING round `rnd`, so possession of all of
    them means every member has COMPLETED every earlier round —
    including broadcast rounds, whose non-src readers nothing else
    synchronizes (a calendar-style "two rounds behind" sweep could
    delete a bcast key a descheduled reader had not consumed, stranding
    it into a spurious CollectiveTimeout on a healthy fleet).  Round
    `rnd` itself is never touched: peers may still be mid-read on it.
    Both collective prefixes share the round counter, so both are
    swept, at most _REAP_BATCH rounds per call (a long broadcast-only
    streak must not turn the next allgather into a delete storm; the
    backlog amortizes over subsequent allgathers).  Known limitation:
    a workload that ONLY broadcasts accrues keys until its next
    allgather/barrier or the namespace reap at finalize/reconfigure —
    keys stay bounded by the namespace lifetime either way.  (Keys a
    mid-round abort leaves behind stay namespaced to this launch id +
    generation, and the whole namespace is reaped on clean exit and on
    reconfigure — this sweep only bounds STEADY-STATE growth.)"""
    if rank != 0:
        return
    from paddle_tpu.resilience import fleet
    ns = fleet.coord_namespace()
    sweep = range(_COORD_REAPED[0] + 1,
                  min(rnd, _COORD_REAPED[0] + 1 + _REAP_BATCH))
    for old in sweep:
        for prefix in (f"{ns}/allgather", f"{ns}/bcast"):
            try:
                client.key_value_delete(f"{prefix}/{old}")
            except Exception:
                pass
    if sweep:
        _COORD_REAPED[0] = sweep[-1]


def _coord_broadcast(value, src):
    """Eager cross-process broadcast over the coordination service:
    only `src` uploads its payload — one set + n gets, instead of the
    n uploads + n*n downloads a full allgather would move through the
    single gRPC coordinator for data only one rank actually has.
    `src` is a FLEET rank (index into the current member list)."""
    import pickle

    import numpy as np

    from paddle_tpu.resilience import fleet

    client = _coord_client()
    wv = fleet.world()
    src_global = wv.members[int(src)]
    _COORD_ROUND[0] += 1
    rnd = _COORD_ROUND[0]
    key = f"{fleet.coord_namespace()}/bcast/{rnd}/{src_global}"
    if wv.global_rank == src_global:
        fleet.kv_set_bytes(client, key, pickle.dumps(np.asarray(value)))
    out = pickle.loads(_coord_get(client, key, src_global, rnd))
    # no reap here: only an allgather proves every member has passed a
    # round (broadcast synchronizes nobody but the reader and src) —
    # _coord_reap fires from _coord_allgather, where the proof holds
    return out


def _process_allgather(value):
    """Backend-appropriate eager cross-process allgather."""
    if jax.default_backend() == "cpu":
        return _coord_allgather(value)
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(value)


def _reduce_fn(op):
    def pprod(v, axis):
        return jnp.exp(jax.lax.psum(jnp.log(v), axis))

    def pavg(v, axis):
        return jax.lax.pmean(v, axis)

    table = {
        ReduceOp.SUM: jax.lax.psum,
        ReduceOp.MAX: jax.lax.pmax,
        ReduceOp.MIN: jax.lax.pmin,
        ReduceOp.AVG: pavg,
        ReduceOp.PROD: pprod,
    }
    if op not in table:
        raise ValueError(f"unsupported ReduceOp {op!r}")
    return table[op]


def _quantized_policy_for(value, op):
    """The active CollectivePolicy when it covers this reduction:
    mesh-axis float SUM/AVG above the policy's size floor.  Everything
    else (integer payloads, MAX/MIN/PROD, tiny tensors, no policy)
    keeps the plain-XLA path — selection is explicit, never ambient."""
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        return None
    from paddle_tpu.quantization.policy import current_collective_policy
    pol = current_collective_policy()
    if pol is None:
        return None
    if not jnp.issubdtype(value.dtype, jnp.floating):
        return None
    if value.size < pol.min_elems:
        return None
    return pol


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _axis_of(group)
    if axis is not None:
        pol = _quantized_policy_for(tensor._value, op)
        if pol is not None:
            # EQuARX-style int8-payload path (quantization/collectives:
            # block-scale -> all_to_all narrow -> f32 reduce -> requant
            # -> all_gather narrow), selected by the trace-scoped
            # quantization.quantized_collectives() policy
            from paddle_tpu.quantization.collectives import \
                quantized_all_reduce
            out = apply(
                lambda v: quantized_all_reduce(
                    v, axis, bits=pol.bits, block=pol.block, key=pol.key,
                    mean=(op == ReduceOp.AVG)).astype(v.dtype), tensor)
            tensor._inplace_assign(out)
            return tensor
        fn = _reduce_fn(op)
        out = apply(lambda v: fn(v, axis), tensor)
        tensor._inplace_assign(out)
        return tensor
    if jax.process_count() > 1:
        g = _process_allgather(tensor._value)
        red = {ReduceOp.SUM: jnp.sum, ReduceOp.MAX: jnp.max,
               ReduceOp.MIN: jnp.min, ReduceOp.PROD: jnp.prod,
               ReduceOp.AVG: jnp.mean}
        if op not in red:
            raise ValueError(f"unsupported ReduceOp {op!r}")
        tensor._set_value(red[op](g, axis=0))
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    axis = _axis_of(group)
    if axis is not None:
        out = apply(lambda v: jax.lax.all_gather(v, axis), tensor)
        n = dmesh.axis_size(axis)
        for i in range(n):
            tensor_list.append(out[i])
        return tensor_list
    if jax.process_count() > 1:
        g = _process_allgather(tensor._value)
        for i in range(g.shape[0]):
            tensor_list.append(Tensor(g[i]))
        return tensor_list
    tensor_list.append(tensor.clone())
    return tensor_list


def all_gather_object(object_list, obj, group=None):
    object_list.append(obj)
    return object_list


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _axis_of(group)
    from paddle_tpu.tensor.manipulation import concat
    stacked = concat(tensor_list, axis=0) if isinstance(tensor_list, (list, tuple)) \
        else tensor_list
    if axis is not None:
        out = apply(lambda v: jax.lax.psum_scatter(v, axis, tiled=True), stacked)
        tensor._inplace_assign(out)
        return tensor
    tensor._set_value(stacked._value)
    return tensor


def broadcast(tensor, src=0, group=None, sync_op=True):
    axis = _axis_of(group)
    if axis is not None:
        def fn(v):
            idx = jax.lax.axis_index(axis)
            return jax.lax.psum(jnp.where(idx == src, v, jnp.zeros_like(v)), axis)
        out = apply(fn, tensor)
        tensor._inplace_assign(out)
        return tensor
    if jax.process_count() > 1:
        if jax.default_backend() == "cpu":
            tensor._set_value(_coord_broadcast(tensor._value, src))
        else:
            from jax.experimental import multihost_utils
            tensor._set_value(
                multihost_utils.broadcast_one_to_all(tensor._value))
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    axis = _axis_of(group)
    if tensor_list is None:
        return tensor
    from paddle_tpu.tensor.manipulation import stack
    stacked = stack(tensor_list, axis=0)
    if axis is not None:
        def fn(v):
            idx = jax.lax.axis_index(axis)
            return jnp.take(v, idx, axis=0)
        out = apply(fn, stacked)
        tensor._inplace_assign(out)
        return tensor
    tensor._set_value(tensor_list[0]._value)
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    axis = _axis_of(group)
    from paddle_tpu.tensor.manipulation import stack
    stacked = stack(in_tensor_list, axis=0) if isinstance(in_tensor_list, (list, tuple)) \
        else in_tensor_list
    if axis is not None:
        out = apply(lambda v: jax.lax.all_to_all(v, axis, split_axis=0,
                                                 concat_axis=0, tiled=False), stacked)
        n = dmesh.axis_size(axis)
        if out_tensor_list is not None:
            for i in range(n):
                out_tensor_list.append(out[i])
            return out_tensor_list
        return out
    if out_tensor_list is not None:
        out_tensor_list.extend([t.clone() for t in in_tensor_list])
        return out_tensor_list
    return stacked


def all_to_all_single(out_tensor, in_tensor, group=None, sync_op=True):
    axis = _axis_of(group)
    if axis is not None:
        out = apply(lambda v: jax.lax.all_to_all(
            v, axis, split_axis=0, concat_axis=0, tiled=True), in_tensor)
        out_tensor._inplace_assign(out)
        return out_tensor
    out_tensor._set_value(in_tensor._value)
    return out_tensor


def send(tensor, dst=0, group=None, sync_op=True):
    axis = _axis_of(group)
    if axis is None:
        raise RuntimeError("send/recv require a mesh axis (pipeline context)")
    # point-to-point on TPU == ppermute ring step; paired with recv
    raise RuntimeError("use paddle_tpu.distributed.p2p.ppermute_send_recv "
                       "inside shard_map (XLA has no one-sided send)")


def recv(tensor, src=0, group=None, sync_op=True):
    return send(tensor, src, group, sync_op)


def ppermute(tensor, perm, axis=None, group=None):
    """TPU-native p2p: permute values along a mesh axis ring (ICI neighbor
    exchange). perm: list of (src, dst)."""
    ax = axis or _axis_of(group)
    return apply(lambda v: jax.lax.ppermute(v, ax, perm), tensor)


def barrier(group=None):
    if jax.process_count() > 1:
        if jax.default_backend() == "cpu":
            # coordination-service barrier: a tiny allgather round is
            # timeout-bounded and fleet-membership-aware, unlike
            # sync_global_devices (which needs an SPMD-capable backend
            # and the full launch-time process set)
            import numpy as np
            _coord_allgather(np.zeros((1,), np.int8))
            return
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")


def wait(tensor, group=None, use_calc_stream=True):
    tensor._value.block_until_ready()
    return tensor


alltoall_single = all_to_all_single  # reference exports both spellings


class shift:
    """Static peer pattern for batch_isend_irecv: every rank r talks to
    (r + offset) % world_size.  XLA's collective-permute takes one STATIC
    global edge list, so per-rank dynamic peer ints (the reference's
    NCCL contract) cannot lower from inside an SPMD region — uniform
    shifts are the expressible (and, for pipelines/rings, the actually
    used) pattern."""

    def __init__(self, offset):
        self.offset = int(offset)


class P2POp:
    """One point-to-point op for batch_isend_irecv (reference
    distributed/communication/batch_isend_irecv.py).  op is
    paddle.distributed.isend or .irecv; peer a `shift(k)` pattern (see
    shift) on the bound mesh axis."""

    def __init__(self, op, tensor, peer, group=None):
        if op not in (isend, irecv):
            raise ValueError("op must be distributed.isend or "
                             "distributed.irecv")
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def isend(tensor, dst=0, group=None):
    """XLA has no one-sided send; use inside batch_isend_irecv, where a
    matched send/recv set becomes ONE ppermute over the mesh axis."""
    raise RuntimeError(
        "isend/irecv cannot run standalone on XLA (no one-sided p2p). "
        "Wrap them in P2POp(...) and run batch_isend_irecv([...]) — the "
        "batch lowers to a single collective-permute over ICI.")


def irecv(tensor, src=0, group=None):
    isend(tensor, src, group)


def batch_isend_irecv(p2p_op_list):
    """Execute matched isend/irecv pairs as ONE XLA collective-permute
    (reference batch_isend_irecv issues grouped NCCL p2p).  Each isend's
    (my_rank -> peer) edge must have the matching irecv posted on the
    destination; here the full edge list is the ppermute perm and every
    irecv tensor is assigned its permuted value.  Must run inside a
    shard_map / collective-axis context so ranks are defined."""
    from paddle_tpu.distributed import mesh as dmesh

    axis = dmesh.current_collective_axis()
    if axis is None:
        g = p2p_op_list[0].group if p2p_op_list else None
        axis = _axis_of(g)
    if axis is None:
        raise RuntimeError("batch_isend_irecv needs a mesh axis: run "
                           "inside shard_map/collective_axis or pass a "
                           "group bound to an axis")
    sends = [p for p in p2p_op_list if p.op is isend]
    recvs = [p for p in p2p_op_list if p.op is irecv]
    if len(sends) != len(recvs):
        raise ValueError(
            f"batch_isend_irecv needs matched send/recv pairs, got "
            f"{len(sends)} isend vs {len(recvs)} irecv — on XLA every "
            f"permuted value must land in a posted recv buffer")
    n = dmesh.axis_size(axis)
    tasks = []
    for s, r in zip(sends, recvs):
        if not isinstance(s.peer, shift) or not isinstance(r.peer, shift):
            raise TypeError(
                "on XLA, P2POp peers must be distributed.shift(offset) "
                "patterns (a collective-permute needs one static global "
                "edge list; absolute per-rank peer ints cannot be read "
                "inside the SPMD region)")
        if (r.peer.offset + s.peer.offset) % n != 0:
            raise ValueError(
                f"mismatched pair: isend shift({s.peer.offset}) delivers "
                f"to rank+{s.peer.offset}, so the matching irecv must be "
                f"shift({-s.peer.offset}), got shift({r.peer.offset})")
        perm = [(rr, (rr + s.peer.offset) % n) for rr in range(n)]
        out = apply(lambda v, p=tuple(perm): jax.lax.ppermute(v, axis, p),
                    s.tensor)
        r.tensor._inplace_assign(out)
        tasks.append(out)
    return tasks


def destroy_process_group(group=None):
    """Drop the installed mesh/groups (reference destroys NCCL comms)."""
    from paddle_tpu.distributed import mesh as dmesh
    if group is None:
        dmesh.set_mesh(None)


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    """CPU-side gloo bootstrap: jax.distributed covers both CPU and TPU
    meshes here, so this is init_parallel_env."""
    from paddle_tpu import distributed as dist
    dist.init_parallel_env()


def gloo_barrier():
    barrier()


def gloo_release():
    return None
