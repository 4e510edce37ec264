"""Comm/compute overlap: decomposed collective matmuls + data-plane ledger.

The GSPMD-default data plane serializes collectives with the matmuls that
depend on them: the tp all-gather finishes before the column matmul starts,
the row matmul finishes before its all-reduce starts. The reference hides
these on dedicated comm streams (``AttnCommRing``-style grouped P2P); the
TPU-native equivalent is the *decomposed collective matmul* (Wang et al.,
"Overlap Communication with Dependent Computation via Decomposition",
ASPLOS'23): a ``shard_map`` ring where each ``ppermute`` hop moves the next
operand chunk while the current chunk's partial matmul runs — the two ops
share no data dependency inside one ring step, so the scheduler (and the
TPU's async collective-permute) overlaps them.

Two ring kernels cover the canonical Megatron pair:

- :func:`ring_ag_matmul` — all-gather→matmul (ColumnParallelLinear with
  Megatron-SP sequence-sharded input): each device matmuls the seq chunk it
  holds while ppermuting it onward; after ``tp`` steps every device has the
  full-sequence output without a standalone all-gather.
- :func:`ring_matmul_rs` — matmul→reduce-scatter (RowParallelLinear): the
  partial-sum accumulator rides the ring, each step adding the local
  partial for the chunk it currently holds; the terminal all-reduce
  decomposes into overlappable hops (plus one tiled all-gather when the
  consumer wants the replicated layout, i.e. sp is off).

Everything here also feeds the **data-plane ledger**: analytic payload
bytes per traced step program (`comm_bytes_total{kind=...}`), DP gradient
sync counts from the delayed-sync wrappers in
``engine.train_step.build_grad_accum_steps``, and the derived
``comm_overlap_ratio`` that ``tools/trace_summary.py`` reports.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

# -- data-plane ledger -------------------------------------------------------
#
# Byte accounting is ANALYTIC: ring kernels record at trace time (shapes are
# static), the grad-sync wrappers record per host-side call. Semantics:
# `comm_bytes_total{kind}` approximates the payload bytes one *executed*
# step/call moves for that collective kind; a re-trace of the same program
# records again (re-traces are themselves counted by `step_traces_total`,
# so the operator can tell). The ledger mirrors the registry so tests
# read it without enabling telemetry.

_LOCK = threading.Lock()
_BYTES: dict[str, int] = {}          # kind -> analytic payload bytes
_OVERLAPPED_BYTES: dict[str, int] = {}   # subset moved on overlap paths
_DP_SYNCS = {"syncs": 0, "updates": 0}
_RING_FALLBACKS: dict[str, int] = {}     # site -> dense-fallback count
_WARNED_FALLBACK_SITES: set = set()


def record_comm_bytes(kind: str, nbytes: int, *,
                      overlapped: bool = False) -> None:
    """Account ``nbytes`` of data-plane traffic under ``kind``.

    ``overlapped``: the bytes move on a comm/compute-overlapping path
    (manual ring, double-buffered pipeline) rather than a serialized
    collective — the numerator of ``comm_overlap_ratio``. Tracked per
    RECORD, so a kind traced both ways (e.g. pp_ppermute with and
    without ``pp_overlap``) is apportioned, not all-or-nothing."""
    nbytes = int(nbytes)
    if nbytes <= 0:
        return
    with _LOCK:
        _BYTES[kind] = _BYTES.get(kind, 0) + nbytes
        if overlapped:
            _OVERLAPPED_BYTES[kind] = \
                _OVERLAPPED_BYTES.get(kind, 0) + nbytes
    from hetu_tpu import telemetry
    if telemetry.enabled():
        telemetry.get_registry().counter(
            "comm_bytes_total",
            "analytic data-plane collective payload bytes").inc(
                nbytes, kind=kind)
        if overlapped:
            telemetry.get_registry().counter(
                "comm_overlapped_bytes_total",
                "data-plane bytes moved on overlapping paths").inc(
                    nbytes, kind=kind)


def record_dp_sync(n: int = 1, *, grad_bytes: int = 0) -> None:
    """Count ``n`` DP gradient reductions (host-side, exact per call)."""
    with _LOCK:
        _DP_SYNCS["syncs"] += n
    from hetu_tpu import telemetry
    if telemetry.enabled():
        telemetry.get_registry().counter(
            "dp_grad_syncs_total",
            "DP gradient reductions issued").inc(n)
    if grad_bytes:
        record_comm_bytes("dp_grad_sync", grad_bytes * n)


def record_optimizer_update(n: int = 1) -> None:
    """Count optimizer updates — the denominator of ``dp_sync_per_step``."""
    with _LOCK:
        _DP_SYNCS["updates"] += n
    from hetu_tpu import telemetry
    if telemetry.enabled():
        telemetry.get_registry().counter(
            "optimizer_updates_total",
            "optimizer updates applied (grad-accum apply steps)").inc(n)


def record_ring_fallback(site: str, detail: str = "") -> None:
    """Count (and warn ONCE per site about) a ring matmul that silently
    degraded to the dense/GSPMD path on shapes the ring cannot split —
    the operator asked for overlap and is not getting it, which used to
    be invisible (ISSUE 4 satellite). Audited by the
    ``tp_ring_fallback_total`` counter; divisible-dim tests assert 0."""
    with _LOCK:
        _RING_FALLBACKS[site] = _RING_FALLBACKS.get(site, 0) + 1
        first = site not in _WARNED_FALLBACK_SITES
        _WARNED_FALLBACK_SITES.add(site)
    from hetu_tpu import telemetry
    if telemetry.enabled():
        telemetry.get_registry().counter(
            "tp_ring_fallback_total",
            "ring collective matmuls that fell back to the dense path "
            "on non-divisible dims").inc(site=site)
    if first:
        import warnings
        warnings.warn(
            f"tp_overlap='ring' fell back to the serialized GSPMD path "
            f"at {site}: {detail} (warned once per site; counted in "
            f"tp_ring_fallback_total)", stacklevel=3)


def ring_fallbacks() -> dict[str, int]:
    with _LOCK:
        return dict(_RING_FALLBACKS)


def comm_stats() -> dict:
    """Ledger snapshot: bytes by kind, overlap ratio, DP sync rate.

    ``overlap_ratio`` mixes recording granularities — ring/pipeline
    bytes land once per trace, grad-sync bytes once per host call — so
    read it within one run mode; per-kind byte totals are always
    unambiguous."""
    with _LOCK:
        by_kind = dict(_BYTES)
        overlapped_by_kind = dict(_OVERLAPPED_BYTES)
        overlapped = sum(overlapped_by_kind.values())
        syncs, updates = _DP_SYNCS["syncs"], _DP_SYNCS["updates"]
        fallbacks = sum(_RING_FALLBACKS.values())
    total = sum(by_kind.values())
    return {
        "bytes_by_kind": by_kind,
        "bytes_total": total,
        "bytes_overlapped": overlapped,
        "bytes_overlapped_by_kind": overlapped_by_kind,
        "overlap_ratio": overlapped / total if total else 0.0,
        "dp_syncs": syncs,
        "optimizer_updates": updates,
        "dp_sync_per_step": syncs / updates if updates else 0.0,
        "tp_ring_fallbacks": fallbacks,
    }


def reset_comm_stats() -> None:
    with _LOCK:
        _BYTES.clear()
        _OVERLAPPED_BYTES.clear()
        _DP_SYNCS["syncs"] = 0
        _DP_SYNCS["updates"] = 0
        _RING_FALLBACKS.clear()
        _WARNED_FALLBACK_SITES.clear()


# -- ring collective matmuls -------------------------------------------------

def _tp_degree(ctx) -> int:
    if ctx is None or not isinstance(ctx.tp, str):
        return 1
    return ctx.mesh.shape.get(ctx.tp, 1)


def ring_column_applicable(ctx, x_shape, w_shape) -> bool:
    """The column AG→matmul ring needs an all-gather to hide: the input
    must be sequence-sharded over tp (Megatron-SP), the seq dim must
    split evenly into (cp·tp) chunks, and the trace must be in a GSPMD
    region (no ambient context = single-device or manual pipeline body,
    where there is nothing to decompose)."""
    ntp = _tp_degree(ctx)
    if ntp <= 1 or not ctx.sp or len(x_shape) != 3:
        return False
    seq_div = ntp
    if isinstance(ctx.seq, str):
        seq_div *= ctx.mesh.shape.get(ctx.seq, 1)
    return x_shape[1] % seq_div == 0 and w_shape[1] % ntp == 0


def ring_row_applicable(ctx, x_shape, w_shape) -> bool:
    """The row matmul→RS ring decomposes the partial-sum all-reduce; it
    needs tp>1, a tp-divisible local sequence, and a tp-divisible
    contraction dim (the weight's row shards)."""
    ntp = _tp_degree(ctx)
    if ntp <= 1 or len(x_shape) != 3:
        return False
    s_local = x_shape[1]
    if isinstance(ctx.seq, str):
        cp = ctx.mesh.shape.get(ctx.seq, 1)
        if s_local % cp:
            return False
        s_local //= cp
    return s_local % ntp == 0 and x_shape[2] % ntp == 0


def maybe_record_column_fallback(ctx, x_shape, w_shape) -> None:
    """Classify a failed column-ring applicability check: with sp on and
    tp>1 on a 3-D input, the ONLY reason the ring is skipped is a
    non-divisible dim — that degradation is counted and warned (a
    missing sp / tp=1 / manual region is a legitimate fall-through,
    not a fallback)."""
    ntp = _tp_degree(ctx)
    if ntp <= 1 or ctx is None or not ctx.sp or len(x_shape) != 3:
        return
    record_ring_fallback(
        "column_ag_matmul",
        f"x{tuple(x_shape)} @ w{tuple(w_shape)} needs seq % "
        f"(cp*tp) == 0 and w.shape[1] % tp == 0 at tp={ntp}")


def maybe_record_row_fallback(ctx, x_shape, w_shape) -> None:
    """Row-ring twin of :func:`maybe_record_column_fallback`: tp>1 on a
    3-D input means only divisibility can have failed."""
    ntp = _tp_degree(ctx)
    if ntp <= 1 or len(x_shape) != 3:
        return
    record_ring_fallback(
        "row_matmul_rs",
        f"x{tuple(x_shape)} @ w{tuple(w_shape)} needs local seq and "
        f"contraction dims divisible by tp={ntp}")


def ring_ag_matmul(x, w, bias=None, *, ctx, out_kind: str = "hidden"):
    """Decomposed all-gather→matmul (ColumnParallelLinear under sp).

    ``x``: (B, S, E) sequence-sharded over (cp, tp) per ``ctx``'s
    "tokens" spec; ``w``: (E, H) column-sharded over tp. Equivalent to
    ``all_gather(x, tp) @ w`` but as a ``tp``-step ring: step *k* matmuls
    the chunk received at step *k-1* while ppermuting it onward — the
    hop hides behind the partial matmul. Per-output-element arithmetic
    is identical to the fused path (the contraction dim is never split),
    so results are bitwise-equal to overlap-off.
    """
    tp = ctx.tp
    mesh = ctx.mesh
    ntp = mesh.shape[tp]
    in_x = ctx.spec("tokens")            # P(batch, (seq, tp), None)
    in_w = P(None, tp)
    in_b = P(tp)
    out = ctx.spec(out_kind)             # P(batch, seq, tp)
    record_comm_bytes(
        "tp_ring_all_gather",
        x.size * x.dtype.itemsize * (ntp - 1) // max(ntp, 1),
        overlapped=True)
    # receive-from-right: after k hops a device holds the chunk that
    # started on rank (r + k) % ntp
    perm = [(i, (i - 1) % ntp) for i in range(ntp)]

    def body(xl, wl, bl):
        r = jax.lax.axis_index(tp)
        s_loc = xl.shape[1]
        y = jnp.zeros((xl.shape[0], s_loc * ntp, wl.shape[1]), xl.dtype)
        cur = xl
        for k in range(ntp):
            # the ppermute moving chunk k+1 and the matmul consuming
            # chunk k only READ `cur` — no dependency, XLA overlaps them
            part = jnp.matmul(cur, wl)
            src = (r + k) % ntp
            y = jax.lax.dynamic_update_slice_in_dim(
                y, part, src * s_loc, 1)
            if k + 1 < ntp:
                cur = jax.lax.ppermute(cur, tp, perm)
        if bl is not None:
            y = y + bl
        return y

    if bias is None:
        fn = shard_map(lambda xl, wl: body(xl, wl, None), mesh=mesh,
                       in_specs=(in_x, in_w), out_specs=out,
                       check_vma=False)
        return fn(x, w)
    fn = shard_map(body, mesh=mesh, in_specs=(in_x, in_w, in_b),
                   out_specs=out, check_vma=False)
    return fn(x, w, bias)


def ring_matmul_rs(x, w, *, ctx):
    """Decomposed matmul→reduce-scatter (RowParallelLinear).

    ``x``: (B, S, H) feature-sharded over tp; ``w``: (H, E) row-sharded.
    The tp-partial sums accumulate around the ring: each step ppermutes
    the accumulator one hop while the local partial matmul for the newly
    held seq chunk computes. With sp the seq-scattered result is the
    final layout; otherwise one tiled all-gather rebuilds the replicated
    output (the all-reduce's second half — the first half is what the
    ring overlapped).
    """
    tp = ctx.tp
    mesh = ctx.mesh
    ntp = mesh.shape[tp]
    in_x = ctx.spec("hidden")            # P(batch, seq, tp)
    in_w = P(tp, None)
    out = ctx.spec("tokens")             # sp: P(batch, (seq, tp), None)
    record_comm_bytes(
        "tp_ring_reduce_scatter",
        x.size // max(x.shape[-1], 1) * w.shape[-1]
        * x.dtype.itemsize * (ntp - 1) // max(ntp, 1),
        overlapped=True)
    perm = [(i, (i + 1) % ntp) for i in range(ntp)]

    def body(xl, wl):
        r = jax.lax.axis_index(tp)
        s_loc = xl.shape[1] // ntp

        def chunk(idx):
            return jax.lax.dynamic_slice_in_dim(xl, idx * s_loc, s_loc, 1)

        # device r holds the accumulator for chunk (r + ntp-1-k) at step
        # k; after ntp-1 hops it lands on its own chunk r fully reduced
        acc = jnp.matmul(chunk((r + ntp - 1) % ntp), wl)
        for k in range(1, ntp):
            # ppermute(acc) and the next partial matmul share no data —
            # the hop hides behind the chunk compute
            acc = jax.lax.ppermute(acc, tp, perm)
            acc = acc + jnp.matmul(chunk((r + ntp - 1 - k) % ntp), wl)
        if not ctx.sp:
            # consumer wants the tp-replicated layout: finish the
            # all-reduce with the (serialized) gather half
            acc = jax.lax.all_gather(acc, tp, axis=1, tiled=True)
        return acc

    fn = shard_map(body, mesh=mesh, in_specs=(in_x, in_w),
                   out_specs=out, check_vma=False)
    return fn(x, w)


# -- per-layer ZeRO-3 parameter gather ring ----------------------------------
#
# The fsdp fallback is one monolithic GSPMD all-gather of every dp-sharded
# param where it is first consumed; the memory-plane formulation (ZeRO
# SC'20 §5.3 prefetch, ROADMAP "per-layer gather formulation") gathers ONE
# block's params at a time, driven from the model's stacked block list
# (``nn.StackedBlocks``), so block k+1's gather rides the ring while block
# k computes. The gather itself is a tp-style ppermute ring (the PR 3
# machinery extended to the parameter axis): ndp-1 hops, each moving one
# 1/ndp param shard, every hop free of data dependencies on the block
# matmuls the scheduler interleaves it with.

def per_layer_gather_specs(stacked_specs):
    """Per-layer gather specs from the STACKED block param specs: drop the
    leading ``layers`` dim entry; leaves whose remaining spec carries no
    ``dp`` component come back as ``P()`` (pass-through — nothing to
    gather). ``make_plan`` stores the result on the ActivationSharding
    context for ``StackedBlocks`` to consume."""
    def per_layer(spec: P) -> P:
        parts = list(spec)[1:]
        while parts and parts[-1] is None:
            parts.pop()
        if any(p == "dp" or (isinstance(p, tuple) and "dp" in p)
               for p in parts):
            return P(*parts)
        return P()

    import jax
    return jax.tree.map(per_layer, stacked_specs,
                        is_leaf=lambda x: isinstance(x, P))


def _dp_dim(spec: P):
    for i, p in enumerate(spec):
        if p == "dp" or (isinstance(p, (tuple, list)) and "dp" in p):
            return i
    return None


def _strip_dp(spec: P) -> P:
    parts = []
    for p in spec:
        if p == "dp":
            parts.append(None)
        elif isinstance(p, (tuple, list)) and "dp" in p:
            rest = tuple(a for a in p if a != "dp")
            parts.append(rest[0] if len(rest) == 1 else (rest or None))
        else:
            parts.append(p)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def ring_gather_block_params(params, specs, *, mesh):
    """All-gather ONE block's dp-sharded param leaves via a ppermute ring.

    ``params``: one layer's param pytree (inside the layer scan);
    ``specs``: matching pytree of per-layer PartitionSpecs
    (:func:`per_layer_gather_specs`) — leaves with a ``dp`` component
    ring-gather, ``P()`` leaves pass through untouched. The ring is a
    fully-manual ``shard_map`` (every mesh axis bound, tp shards ring
    over dp independently) so the hops lower to async collective-permutes
    a latency-hiding scheduler can slide under block compute.

    Backward: gathering is the identity on values — the registered VJP
    re-constrains each cotangent to the dp-sharded layout, which is
    exactly ZeRO-3's reduce-scattered gradient (the cross-dp sum is
    produced upstream where GSPMD resolves the replicated cotangent), so
    no gradient bytes ride the ring twice.
    """
    ndp = mesh.shape.get("dp", 1)
    leaves = jax.tree.leaves(params)
    spec_leaves = jax.tree.leaves(specs,
                                  is_leaf=lambda x: isinstance(x, P))
    if len(leaves) != len(spec_leaves):
        raise ValueError(
            f"fsdp gather specs do not match block params "
            f"({len(spec_leaves)} specs vs {len(leaves)} leaves)")
    ring_idx = [i for i, s in enumerate(spec_leaves)
                if _dp_dim(s) is not None]
    if ndp <= 1 or not ring_idx:
        return params
    ring_specs = [spec_leaves[i] for i in ring_idx]
    dims = [_dp_dim(s) for s in ring_specs]
    out_specs = tuple(_strip_dp(s) for s in ring_specs)
    # receive-from-right: after k hops a device holds the shard that
    # started on dp rank (r + k) % ndp (same orientation as the tp rings)
    perm = [(i, (i - 1) % ndp) for i in range(ndp)]

    def ring_body(*locs):
        r = jax.lax.axis_index("dp")
        outs = []
        for pl, d in zip(locs, dims):
            chunk = pl.shape[d]
            full = list(pl.shape)
            full[d] = chunk * ndp
            out = jnp.zeros(tuple(full), pl.dtype)
            cur = pl
            for k in range(ndp):
                # the ppermute moving shard k+1 and the update placing
                # shard k only READ `cur` — no dependency, XLA overlaps
                src = (r + k) % ndp
                out = jax.lax.dynamic_update_slice_in_dim(
                    out, cur, src * chunk, d)
                if k + 1 < ndp:
                    cur = jax.lax.ppermute(cur, "dp", perm)
            outs.append(out)
        return tuple(outs)

    sm = shard_map(ring_body, mesh=mesh,
                   in_specs=tuple(ring_specs), out_specs=out_specs,
                   check_vma=False)

    @jax.custom_vjp
    def gathered(*locs):
        return sm(*locs)

    def _fwd(*locs):
        return sm(*locs), None

    def _bwd(_, cts):
        from jax.sharding import NamedSharding
        return tuple(
            jax.lax.with_sharding_constraint(ct, NamedSharding(mesh, s))
            for ct, s in zip(cts, ring_specs))

    gathered.defvjp(_fwd, _bwd)
    out = gathered(*[leaves[i] for i in ring_idx])
    merged = list(leaves)
    for i, g in zip(ring_idx, out):
        merged[i] = g
    return jax.tree.unflatten(jax.tree.structure(params), merged)


def record_fsdp_gather_bytes(params, specs, ndp: int, *,
                             n_layers: float = 1.0,
                             overlapped: bool = True) -> None:
    """Analytic byte accounting for the fsdp param gathers of one traced
    step: each device receives (ndp-1)/ndp of every dp-sharded leaf.
    Pass the STACKED block tree with ``n_layers=1`` (leaf sizes already
    include the layer dim) or a single layer's tree with the stack
    depth; fractional multipliers account regather-in-backward layers
    (gathered twice per step under remat)."""
    if ndp <= 1:
        return
    leaves = jax.tree.leaves(params)
    spec_leaves = jax.tree.leaves(specs,
                                  is_leaf=lambda x: isinstance(x, P))
    if len(leaves) != len(spec_leaves):
        return
    nbytes = 0
    for leaf, spec in zip(leaves, spec_leaves):
        if _dp_dim(spec) is None:
            continue
        size = 1
        for d in leaf.shape:
            size *= int(d)
        nbytes += size * leaf.dtype.itemsize * (ndp - 1) // ndp
    record_comm_bytes("fsdp_gather", int(nbytes * n_layers),
                      overlapped=overlapped)
