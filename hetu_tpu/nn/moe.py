"""Mixture-of-Experts with expert parallelism.

Parity target: HetuMoE (reference ``hetu/v1``): top-k gates
(``v1/python/hetu/layers/*Gate.py``), all-to-all expert dispatch
(``v1/python/hetu/gpu_ops/AllToAll.py``, backend primitive
``nccl_comm_group.h:44``), examples ``v1/examples/moe/``. The v2 graph layer
has no MoE — this module is the capability re-designed TPU-first:

- Router + load-balance aux loss computed on the GLOBAL token array under
  GSPMD (cheap; numerically identical across strategies).
- Dispatch/combine run inside a *partial-manual* ``shard_map`` over
  {dp, ep}: tokens scatter into per-expert capacity buffers via one-hot
  matmuls (MXU-friendly), ``jax.lax.all_to_all`` over the ep axis moves
  token blocks to the ranks owning their experts, expert FFNs apply
  batched (their tp-sharded dims stay GSPMD-auto), and a second
  all_to_all returns results for the weighted combine.
- Expert params are stacked on a leading ``expert`` axis (rule
  ``"expert" → "ep"``), so checkpoint/resharding treat them like any other
  param.
- ``Strategy(ep_overlap="chunk")`` decomposes the dispatch-a2a → expert
  FFN → combine-a2a chain into ``ep_chunks`` capacity slices: chunk *i*'s
  combine-a2a (and chunk *i+1*'s dispatch-a2a) share no data with chunk
  *i*'s expert matmul, so the scheduler (and the TPU's async all_to_all)
  hides the exchanges behind compute — the EP twin of the PR 3/4 tp/fsdp
  rings, bitwise-identical to the serialized path (capacity slices are
  disjoint and the combine consumes the re-concatenated buffer). The
  analytic ledger audits it as ``comm_bytes_total{kind="ep_a2a"}`` with
  the overlapped split.
- The expert plane is observable: per-expert load gauges
  (``moe_expert_tokens{expert}``), the capacity-overflow counter
  (``moe_dropped_tokens_total`` — tokens past the capacity buffer used
  to vanish silently), and aux-loss/overflow-fraction histograms are
  emitted through a trace-time-gated ``jax.debug.callback`` when
  telemetry is enabled.
- :class:`ExpertShareMoE` is the SERVING form of expert parallelism on
  one chip of a wide-EP deployment: the layer is told which experts it
  holds, routes over all of them without dropping a token, and computes
  its own experts' part of the result through a sorted, grouped matmul
  (``jax.lax.ragged_dot``). Nothing stands in for the absent chips.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from hetu_tpu.nn.module import Module, StackedLeaf, normal_init
from hetu_tpu.ops import activations as act_ops
from hetu_tpu.parallel.sharding import (
    act_constrain, current_act_sharding, current_manual_axes,
)


class TopKGate(Module):
    """Softmax router with top-k selection and GShard/Switch aux loss.

    Reference gates: ``TopGate``/``KTop1Gate``/``BalanceGate``
    (``hetu/v1/python/hetu/layers/``).
    """

    def __init__(self, features: int, num_experts: int, k: int = 2,
                 init=None):
        super().__init__()
        self.num_experts = num_experts
        self.k = k
        self.param("weight", (features, num_experts),
                   init or normal_init(0.02), axes=("embed", None))

    def __call__(self, params, x):
        """x (T, d) → (idx (T,k) int32, weights (T,k) fp32, aux scalar)."""
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            params["weight"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_idx = jax.lax.top_k(probs, self.k)
        if self.k > 1:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        # load-balance aux (Switch/GShard): E * Σ_e f_e · P_e, with f from
        # first-choice assignments
        first = jax.nn.one_hot(top_idx[:, 0], self.num_experts,
                               dtype=jnp.float32)
        f_e = jnp.mean(first, axis=0)
        p_e = jnp.mean(probs, axis=0)
        aux = self.num_experts * jnp.sum(f_e * p_e)
        return top_idx.astype(jnp.int32), top_w, aux


class KTop1Gate(Module):
    """k independent top-1 routers over disjoint expert groups.

    Reference: ``KTop1Gate`` (``hetu/v1/python/hetu/layers/KTop1Gate.py``,
    ``ktop1gating``): the E logits split into k prototype groups of E/k
    experts; each group runs its own softmax + top-1, so a token gets
    exactly one expert PER GROUP (cheaper top-1 selection, top-k-like
    capacity). Gate weight = the group softmax prob of the selected
    expert (raw, not renormalized across groups — reference ``gates_s``);
    aux = sum of per-group balance losses."""

    def __init__(self, features: int, num_experts: int, k: int = 2,
                 init=None):
        super().__init__()
        if num_experts % k != 0:
            raise ValueError(f"num_experts {num_experts} must divide by "
                             f"k {k} prototype groups")
        self.num_experts = num_experts
        self.k = k
        self.param("weight", (features, num_experts),
                   init or normal_init(0.02), axes=("embed", None))

    def __call__(self, params, x):
        T = x.shape[0]
        Eg = self.num_experts // self.k
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            params["weight"].astype(jnp.float32))
        # (T, k, E/k): group g owns experts [g*Eg, (g+1)*Eg)
        probs = jax.nn.softmax(logits.reshape(T, self.k, Eg), axis=-1)
        local = jnp.argmax(probs, axis=-1)              # (T, k)
        w = jnp.take_along_axis(probs, local[..., None],
                                axis=-1)[..., 0]        # (T, k)
        offs = jnp.arange(self.k, dtype=jnp.int32) * Eg
        idx = local.astype(jnp.int32) + offs[None, :]
        first = jax.nn.one_hot(local, Eg, dtype=jnp.float32)  # (T,k,Eg)
        f_e = jnp.mean(first, axis=0)                   # (k, Eg)
        p_e = jnp.mean(probs, axis=0)
        aux = Eg * jnp.sum(f_e * p_e)                   # summed over groups
        return idx, w, aux


class SAMGate(Module):
    """Locality-aware gate: pick ONE expert group (device), then top-k
    within it.

    Reference: ``SAMGate`` (``hetu/v1/python/hetu/layers/SAMGate.py``,
    ``samgating``): softmax over all E experts; experts are grouped by
    owning device (``num_local_gpus`` groups); the group with the largest
    total gate mass wins (``sam_group_sum_op`` + top-1), then the top-k
    experts INSIDE that group are used — so all k experts of a token live
    on one device and dispatch needs no cross-group traffic. Aux combines
    the balance loss with an alignment term (``sam_max_op``) pushing gate
    mass into the chosen group; here alignment = mean out-of-group mass
    (a TPU-friendly closed form with the same gradient direction)."""

    def __init__(self, features: int, num_experts: int, k: int = 2,
                 num_groups: int = 2, alignment_coef: float = 1.0,
                 init=None):
        super().__init__()
        if num_experts % num_groups != 0:
            raise ValueError(f"num_experts {num_experts} must divide by "
                             f"num_groups {num_groups}")
        if k > num_experts // num_groups:
            raise ValueError("k cannot exceed experts per group")
        self.num_experts = num_experts
        self.k = k
        self.num_groups = num_groups
        self.alignment_coef = alignment_coef
        self.param("weight", (features, num_experts),
                   init or normal_init(0.02), axes=("embed", None))

    def __call__(self, params, x):
        T = x.shape[0]
        G, Eg = self.num_groups, self.num_experts // self.num_groups
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            params["weight"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)         # (T, E)
        pg = probs.reshape(T, G, Eg)
        group_mass = jnp.sum(pg, axis=-1)               # (T, G)
        g_star = jnp.argmax(group_mass, axis=-1)        # (T,)
        in_group = jnp.take_along_axis(
            pg, g_star[:, None, None], axis=1)[:, 0]    # (T, Eg)
        w, local = jax.lax.top_k(in_group, self.k)      # raw probs
        idx = (local + (g_star[:, None] * Eg)).astype(jnp.int32)
        first = jax.nn.one_hot(idx[:, 0], self.num_experts,
                               dtype=jnp.float32)
        aux = self.num_experts * jnp.sum(
            jnp.mean(first, axis=0) * jnp.mean(probs, axis=0))
        out_of_group = 1.0 - jnp.take_along_axis(
            group_mass, g_star[:, None], axis=1)[:, 0]
        aux = aux + self.alignment_coef * jnp.mean(out_of_group)
        return idx, w, aux


class BalanceGate(Module):
    """Balanced-assignment routing (BASE-layers style), Sinkhorn form.

    Reference: ``BalanceAssignmentGate``
    (``hetu/v1/python/hetu/layers/BalanceGate.py``): token-expert affinity
    ``x @ centroids^T`` solved to a BALANCED assignment (every expert gets
    T/E tokens) by a native auction solver (``balance_assignment_op``).
    The TPU-native re-design replaces the sequential auction with fixed
    Sinkhorn iterations (row/col renormalization — pure matmul/softmax,
    jit- and MXU-friendly), then takes the per-token argmax of the
    transport plan; weight = sigmoid(affinity) as in BASE. k = 1, aux = 0
    (balance is enforced by construction, approximately under Sinkhorn)."""

    #: routing depends on the WHOLE co-batched row set (the Sinkhorn
    #: column marginal couples tokens) — decode paths that pack rows
    #: from unrelated requests must refuse this gate (MoEMLP.decode)
    batch_coupled = True

    def __init__(self, features: int, num_experts: int, *,
                 n_iters: int = 24, temperature: float = 0.02, init=None):
        # defaults measured (CPU sweep, r4): τ=0.02/24 iters → ~0.8%
        # capacity drop at factor 1.0 and load imbalance 1.03, vs 10%/1.31
        # for plain argmax — cold Sinkhorn ≈ the exact auction assignment
        super().__init__()
        self.num_experts = num_experts
        self.k = 1
        self.n_iters = n_iters
        self.temperature = temperature
        self.param("centroids", (num_experts, features),
                   init or normal_init(0.02), axes=(None, "embed"))

    def __call__(self, params, x):
        T = x.shape[0]
        scores = jnp.einsum("td,ed->te", x.astype(jnp.float32),
                            params["centroids"].astype(jnp.float32))
        # Sinkhorn to (approx) uniform marginals: rows sum to 1 (each
        # token routed once), cols to T/E (balanced expert load)
        logp = scores / self.temperature

        def body(logp, _):
            logp = jax.nn.log_softmax(logp, axis=1)       # row normalize
            logp = logp - jax.nn.logsumexp(logp, axis=0,
                                           keepdims=True) \
                + jnp.log(T / self.num_experts)            # col marginal
            return logp, None

        logp, _ = jax.lax.scan(body, logp, None, length=self.n_iters)
        idx = jnp.argmax(logp, axis=-1).astype(jnp.int32)[:, None]
        aff = jnp.take_along_axis(scores, idx, axis=-1)
        w = jax.nn.sigmoid(aff)
        return idx, w, jnp.zeros([], jnp.float32)


GATE_TYPES = {"topk": TopKGate, "ktop1": KTop1Gate, "sam": SAMGate,
              "balance": BalanceGate}


def make_gate(gate_type: str, features: int, num_experts: int,
              k: int = 2, **kw) -> Module:
    """Gate factory for config-driven model construction."""
    if gate_type not in GATE_TYPES:
        raise ValueError(f"unknown gate {gate_type!r}; "
                         f"have {sorted(GATE_TYPES)}")
    if gate_type == "balance":
        if k != 1:
            # not an error: k=2 is the untouched config default, so a
            # hard reject would break moe_gate="balance" out of the box —
            # but the downgrade must be visible
            import warnings
            warnings.warn(
                f"balance gate is top-1 by construction (BASE layers); "
                f"requested k={k} is downgraded to 1 (capacity and "
                f"per-token compute follow)", stacklevel=2)
        return BalanceGate(features, num_experts, **kw)
    return GATE_TYPES[gate_type](features, num_experts, k=k, **kw)


def gate_drop_stats(idx, num_experts: int, k: int,
                    capacity_factor: float) -> dict:
    """Capacity-drop statistics for a gate decision (surfaced in metrics
    / the EP workload): fraction of (token, choice) slots dropped by the
    capacity limit, plus the per-expert load histogram. Mirrors the
    position computation of ``_ep_dispatch`` exactly."""
    T = idx.shape[0]
    E = num_experts
    C = max(1, math.ceil(capacity_factor * T * k / E))
    idx_f = idx.reshape(T * k)
    oh = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(T * k), idx_f]
    dropped = (pos >= C)
    load = jnp.sum(oh, axis=0)
    return {
        "drop_frac": jnp.mean(dropped.astype(jnp.float32)),
        "expert_load": load,
        "load_imbalance": load.max() / jnp.maximum(1, load.mean()),
        "capacity": C,
    }


def _emit_expert_plane(load, dropped, aux):
    """Host side of the expert-plane telemetry callback (values arrive
    as numpy arrays via ``jax.debug.callback``)."""
    from hetu_tpu import telemetry
    if not telemetry.enabled():
        return
    import numpy as np
    reg = telemetry.get_registry()
    load = np.asarray(load)
    gauge = reg.gauge(
        "moe_expert_tokens",
        "tokens routed to each expert on the last observed MoE layer "
        "call (pre-capacity, global batch)")
    for e, n in enumerate(load.tolist()):
        gauge.set(float(n), expert=str(e))
    d = float(dropped)
    if d:
        reg.counter(
            "moe_dropped_tokens_total",
            "(token, choice) slots dropped by the EP capacity limit "
            "— contributions that silently vanish from the combine").inc(d)
    total = float(load.sum())
    reg.histogram(
        "moe_overflow_fraction",
        "fraction of (token, choice) slots dropped by the capacity "
        "limit, per MoE layer call").observe(d / max(total, 1.0))
    reg.histogram(
        "moe_aux_loss",
        "MoE load-balance aux loss per layer call").observe(float(aux))


@jax.custom_vjp
def _expert_plane_probe(out, load, dropped, aux):
    """Identity on ``out`` that emits the expert-plane stats exactly
    once per executed layer call, in BOTH execution modes:

    - un-differentiated traces (eval, the dense decode oracle, bench
      forwards) run the primal — the ``jax.debug.callback`` here fires;
    - differentiated traces replace the primal with the fwd/bwd pair,
      and the emission moves to the BACKWARD: under jax 0.4.37 an
      effect inside a scan body is silently dropped by partial-eval
      when the scan is differentiated (the train step's layer scan!),
      but the transposed backward scan executes its own effects — so
      the bwd is where training-step stats must be emitted. A remat
      forward replay runs the (emission-free) fwd, never the primal,
      so recompute cannot double-count.

    ``load``/``dropped``/``aux`` must be float arrays (their zero
    cotangents are returned as-is)."""
    jax.debug.callback(_emit_expert_plane, load, dropped, aux)
    return out


def _probe_fwd(out, load, dropped, aux):
    return out, (load, dropped, aux)


def _probe_bwd(res, ct):
    load, dropped, aux = res
    jax.debug.callback(_emit_expert_plane, load, dropped, aux)
    return (ct, jnp.zeros_like(load), jnp.zeros_like(dropped),
            jnp.zeros_like(aux))


_expert_plane_probe.defvjp(_probe_fwd, _probe_bwd)


def _expert_plane_stats(idx, *, num_experts: int, k: int,
                        capacity_factor: float, n_shards: int):
    """Traced expert-plane stats for one MoE layer call: global
    per-expert load plus the EXACT dropped-slot count of the EP dispatch
    — the position computation of :func:`_ep_dispatch` replayed per
    batch shard (the token dim is contiguously sharded over dp×ep, so
    shard r's rows are ``idx[r*Tl:(r+1)*Tl]``). ``n_shards=0`` marks the
    capacity-free dense-oracle path (nothing drops)."""
    T = idx.shape[0]
    E = num_experts
    oh_flat = jax.nn.one_hot(idx.reshape(T * k), E, dtype=jnp.int32)
    load = jnp.sum(oh_flat, axis=0)
    if n_shards <= 0 or T % n_shards:
        return load, jnp.zeros([], jnp.int32)
    Tl = T // n_shards
    C = max(1, math.ceil(capacity_factor * Tl * k / E))
    idx_s = idx.reshape(n_shards, Tl * k)
    oh = jax.nn.one_hot(idx_s, E, dtype=jnp.int32)   # (S, Tlk, E)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=1) - oh,
                              idx_s[..., None], axis=2)[..., 0]
    dropped = jnp.sum((pos >= C).astype(jnp.int32))
    return load, dropped


class HashGate(Module):
    """Deterministic hash routing (reference ``HashGate``): expert =
    token_id mod E. Needs token ids, so it routes on provided ids rather
    than hidden states; aux loss is zero."""

    def __init__(self, num_experts: int):
        super().__init__()
        self.num_experts = num_experts
        self.k = 1

    def __call__(self, params, token_ids):
        idx = (token_ids.reshape(-1, 1) % self.num_experts).astype(jnp.int32)
        w = jnp.ones(idx.shape, jnp.float32)
        return idx, w, jnp.zeros([], jnp.float32)


class MoEMLP(Module):
    """Expert-parallel FFN layer (drop-in for ParallelMLP; returns
    ``(out, aux_loss)``)."""

    returns_aux = True

    def __init__(self, features: int, hidden: int, num_experts: int, *,
                 k: int = 2, capacity_factor: float = 1.25,
                 gated: bool = False, gate_type: str = "topk",
                 gate_kwargs: Optional[dict] = None, init=None):
        super().__init__()
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.gated = gated
        self.activation = act_ops.swiglu if gated else jax.nn.gelu
        init = init or normal_init(0.02)
        self.gate = make_gate(gate_type, features, num_experts, k=k,
                              **(gate_kwargs or {}))
        self.k = self.gate.k      # balance gate forces k=1
        self.param("wi", (num_experts, features, hidden), init,
                   axes=("expert", "embed", "mlp"))
        if gated:
            self.param("wg", (num_experts, features, hidden), init,
                       axes=("expert", "embed", "mlp"))
        self.param("wo", (num_experts, hidden, features), init,
                   axes=("expert", "mlp", "embed"))

    # -- expert application (local experts, batched tokens) ---------------
    def _apply_experts(self, params, xe):
        """xe (E_local, C_tot, d) → (E_local, C_tot, d)."""
        dt = self.compute_dtype()
        h = jnp.einsum("ecd,edh->ech", xe.astype(dt),
                       params["wi"].astype(dt))
        if self.gated:
            g = jnp.einsum("ecd,edh->ech", xe.astype(dt),
                           params["wg"].astype(dt))
            h = self.activation(g, h)
        else:
            h = self.activation(h)
        return jnp.einsum("ech,ehd->ecd", h, params["wo"].astype(dt))

    def _expert_params(self, params):
        return {n: params[n] for n in
                (("wi", "wg", "wo") if self.gated else ("wi", "wo"))}

    @staticmethod
    def _ep_axes_of(mesh) -> tuple:
        """("ep",) for the flat axis, ("ep_out", "ep_in") when the mesh
        factors expert parallelism for the hierarchical a2a (multi-slice:
        ep_out across DCN, ep_in within a slice), () when absent."""
        if mesh.shape.get("ep", 1) > 1:
            return ("ep",)
        if "ep_out" in mesh.shape and "ep_in" in mesh.shape \
                and mesh.shape["ep_out"] * mesh.shape["ep_in"] > 1:
            return ("ep_out", "ep_in")
        return ()

    @staticmethod
    def _ep_degree(mesh, axes) -> int:
        n = 1
        for a in axes:
            n *= mesh.shape.get(a, 1)
        return n

    def __call__(self, params, x):
        b, s, d = x.shape
        xf = x.reshape(b * s, d)
        idx, wgt, aux = self.gate(params["gate"], xf)

        # inside a manual region (the pipeline executor, or the delayed
        # grad-sync body) with a manual ep axis: run the dispatch body
        # directly on the bound axis — the EP x PP / EP x delayed-sync
        # composition (no nested shard_map allowed). Telemetry callbacks
        # stay out of manual regions (SPMD partitioning of the auto axes
        # rejects the callback custom-call under jax 0.4.37).
        man = current_manual_axes()
        if man is not None:
            axes = self._ep_axes_of(man.mesh)
            ep = self._ep_degree(man.mesh, axes)
            if axes and set(axes) <= man.axes and ep > 1 \
                    and self.num_experts % ep == 0:
                out = _ep_dispatch(
                    xf, idx, wgt, self._expert_params(params),
                    ep=ep, num_experts=self.num_experts,
                    k=self.k, capacity_factor=self.capacity_factor,
                    apply_experts=self._apply_experts, ep_axes=axes,
                    ep_overlap=getattr(man, "ep_overlap", "off"),
                    ep_chunks=getattr(man, "ep_chunks", 2))
                aux = jax.lax.pmean(aux, axes)
                return out.reshape(b, s, d).astype(x.dtype), aux

        ctx = current_act_sharding()
        ep_deg = 0
        axes = ()
        if ctx is not None:
            axes = self._ep_axes_of(ctx.mesh)
            ep_deg = self._ep_degree(ctx.mesh, axes) if axes else 0
            if ep_deg > 1 and self.num_experts % ep_deg != 0:
                ep_deg = 0

        if ep_deg > 1:
            out = self._ep_forward(params, xf, idx, wgt, ctx, axes, ep_deg)
        else:
            out = self._dense_forward(params, xf, idx, wgt)

        from hetu_tpu import telemetry
        if telemetry.enabled():
            # expert-plane observability: per-expert load + the EXACT
            # dropped-slot count of the EP dispatch (0 on the capacity-
            # free dense oracle). Trace-time gated; emission routed
            # through the custom_vjp probe so differentiated layer
            # scans still fire it (and remat cannot double-count).
            n_shards = 0
            if ep_deg > 1:
                n_shards = ep_deg * ctx.mesh.shape.get("dp", 1)
            load, dropped = _expert_plane_stats(
                idx, num_experts=self.num_experts, k=self.k,
                capacity_factor=self.capacity_factor, n_shards=n_shards)
            out = _expert_plane_probe(
                out, load.astype(jnp.float32),
                dropped.astype(jnp.float32), aux)

        out = act_constrain(out.reshape(b, s, d).astype(x.dtype), "tokens")
        return out, aux

    # -- decode path (serving / autoregressive generation) ------------------
    def prequantize(self, params, *, stacked: bool = False):
        """Quantize the expert FFN stacks ONCE into the W8A8 decode
        lane's ``{name: {"q": int8, "scale": fp32}}`` tree.

        Per-(expert, output-channel) symmetric scales over each
        einsum's contraction axis: ``wi``/``wg`` (E, d, H) quantize
        over d (scale (E, 1, H)), ``wo`` (E, H, d) over H (scale
        (E, 1, d)); a stacked (L, E, ...) tree shifts the axis by one.
        The decode gather then moves int8 expert slices — 1/4 the HBM
        bytes of the fp32 gather, which is where MoE decode time goes."""
        from hetu_tpu.ops.quantization import quantize_int8
        axis = 2 if stacked else 1
        names = ["wi", "wo"] + (["wg"] if self.gated else [])
        return {
            name: dict(zip(("q", "scale"),
                           quantize_int8(params[name], axis=axis)))
            for name in names
        }

    def decode(self, params, x, *, w8a8=None, wq=None):
        """Per-row top-k through GATHERED local-expert einsums — the
        decode-mode twin of the dense oracle that computes only the k
        selected experts per token (O(T·k) FFNs instead of O(T·E)).

        The serving engine's fused step (and one-shot ``generate``) call
        the transformer blocks in kv-cache mode with a handful of slot
        rows; experts are stacked params on the leading ``expert`` axis,
        so per-row routing is a ``jnp.take`` of (k, d, h) weight slices
        plus batched einsums. The combine accumulates the same
        ``Σ_j w_j·expert_{idx_j}(x)`` the dense oracle produces (k ≤ 2
        keeps fp addition commutative), so greedy serving tokens match
        one-shot generation. Returns the output only — aux is
        train-only.

        ``w8a8`` (traced bool) + ``wq`` (a :meth:`prequantize` tree)
        select the quantized-compute lane per call: expert slices
        gather as int8, activations quantize per token, and both
        expert einsums contract int8×int8 with int32 accumulation —
        the MoE extension of ``ParallelMLP``'s W8A8 decode lane. The
        gate always routes in fp (routing flips would change WHICH
        experts run, not just their arithmetic)."""
        if getattr(self.gate, "batch_coupled", False):
            raise NotImplementedError(
                f"MoEMLP.decode needs a per-token gate; "
                f"{type(self.gate).__name__} routes over the whole "
                "co-batched row set, so serving outputs would depend on "
                "which requests share the fused step and could never "
                "match one-shot generate")
        b, s, d = x.shape
        xf = x.reshape(b * s, d)
        idx, wgt, _ = self.gate(params["gate"], xf)
        dt = self.compute_dtype()
        xc = xf.astype(dt)

        def fp_lane(params, xc):
            wi = jnp.take(params["wi"], idx, axis=0).astype(dt)  # (T,k,d,H)
            h = jnp.einsum("td,tkdh->tkh", xc, wi)
            if self.gated:
                wg = jnp.take(params["wg"], idx, axis=0).astype(dt)
                g = jnp.einsum("td,tkdh->tkh", xc, wg)
                h = self.activation(g, h)
            else:
                h = self.activation(h)
            wo = jnp.take(params["wo"], idx, axis=0).astype(dt)  # (T,k,H,d)
            y = jnp.einsum("tkh,tkhd->tkd", h, wo)
            return jnp.sum(wgt[..., None] * y.astype(jnp.float32), axis=1)

        def q_lane(params, xc):
            from hetu_tpu.ops.quantization import quantize_int8
            xq, xs = quantize_int8(xc, axis=-1)          # (T,d), (T,1)

            def up(name):
                wq_e = jnp.take(wq[name]["q"], idx, axis=0)      # int8
                ws_e = jnp.take(wq[name]["scale"], idx, axis=0)  # (T,k,1,H)
                acc = jnp.einsum("td,tkdh->tkh", xq, wq_e,
                                 preferred_element_type=jnp.int32)
                return (acc.astype(jnp.float32)
                        * xs[:, :, None] * ws_e[:, :, 0, :])

            h = up("wi")
            if self.gated:
                h = self.activation(up("wg"), h)
            else:
                h = self.activation(h)
            hq, hs = quantize_int8(h, axis=-1)           # (T,k,H), (T,k,1)
            wo_q = jnp.take(wq["wo"]["q"], idx, axis=0)
            wo_s = jnp.take(wq["wo"]["scale"], idx, axis=0)  # (T,k,1,d)
            acc = jnp.einsum("tkh,tkhd->tkd", hq, wo_q,
                             preferred_element_type=jnp.int32)
            y = acc.astype(jnp.float32) * hs * wo_s[:, :, 0, :]
            return jnp.sum(wgt[..., None] * y, axis=1)

        if w8a8 is None or wq is None:
            out = fp_lane(params, xc)
        else:
            out = jax.lax.cond(
                w8a8, lambda p, v: q_lane(p, v), fp_lane, params, xc)
        return out.reshape(b, s, d).astype(x.dtype)

    # -- dense oracle (single device / no ep axis): every expert computes
    # every token, combine by gate weights — capacity-free ------------------
    def _dense_forward(self, params, xf, idx, wgt):
        xe = jnp.broadcast_to(xf[None], (self.num_experts, *xf.shape))
        ye = self._apply_experts(params, xe)         # (E, T, d)
        combine = jnp.zeros((xf.shape[0], self.num_experts), jnp.float32)
        for j in range(self.k):
            combine = combine + wgt[:, j, None] * jax.nn.one_hot(
                idx[:, j], self.num_experts, dtype=jnp.float32)
        return jnp.einsum("te,etd->td", combine, ye.astype(jnp.float32))

    # -- expert-parallel path: capacity buffers + all_to_all ----------------
    def _ep_forward(self, params, xf, idx, wgt, ctx, ep_axes, ep_deg):
        expert_params = self._expert_params(params)
        tok_spec = P(("dp",) + tuple(ep_axes))
        exp_spec = jax.tree.map(lambda _: P(tuple(ep_axes)),
                                expert_params)
        body = functools.partial(
            _ep_dispatch, ep=ep_deg,
            num_experts=self.num_experts, k=self.k,
            capacity_factor=self.capacity_factor,
            apply_experts=self._apply_experts, ep_axes=ep_axes,
            ep_overlap=getattr(ctx, "ep_overlap", "off"),
            ep_chunks=getattr(ctx, "ep_chunks", 2))

        fn = shard_map(
            body, mesh=ctx.mesh,
            in_specs=(tok_spec, tok_spec, tok_spec, exp_spec),
            out_specs=tok_spec, axis_names={"dp", *ep_axes},
            check_vma=False)
        return fn(xf, idx, wgt, expert_params)


def _bound_axis_size(name: str) -> int:
    """Size of a bound manual axis. ``jax.lax.axis_size`` only exists
    on jax >= 0.6 (the tree's target); under the 0.4.37 container the
    ``psum(1, axis)`` idiom returns the same static int — this gap made
    the factored-ep (multi-slice) path raise AttributeError until the
    ISSUE 9 quick-tier unit test caught it."""
    if hasattr(jax.lax, "axis_size"):
        return jax.lax.axis_size(name)
    return jax.lax.psum(1, name)


def hierarchical_all_to_all(buf, outer_axis: str, inner_axis: str):
    """Two-stage all_to_all over a FACTORED expert axis (ep = outer ×
    inner): exchange over the inner (intra-slice, ICI) axis first, then
    the outer (cross-slice, DCN) axis — so the DCN stage moves one large
    contiguous block per destination slice instead of ep small ones.

    Reference capability: the hierarchical a2a of HetuMoE
    (``hetu/v1/python/hetu/gpu_ops/AllToAll.py`` over grouped NCCL comms).
    ``buf``: (ep, ...) per-rank blocks, destination-major with rank
    r = outer * inner_size + inner. Returns the same shape with the
    leading dim indexing sources."""
    ep = buf.shape[0]
    O = _bound_axis_size(outer_axis)
    I = _bound_axis_size(inner_axis)
    assert O * I == ep, (O, I, ep)
    b = buf.reshape((O, I) + buf.shape[1:])
    # inner exchange delivers each (outer-dest, inner-dest) block to the
    # right inner rank within the source slice...
    b = jax.lax.all_to_all(b, inner_axis, split_axis=1, concat_axis=1)
    # ...then one aggregated block per destination slice rides DCN
    b = jax.lax.all_to_all(b, outer_axis, split_axis=0, concat_axis=0)
    return b.reshape((ep,) + buf.shape[1:])


@jax.custom_vjp
def _pin_buffer(x):
    """Differentiable ``optimization_barrier``: identity that stops XLA
    fusing/splitting ops across the pinned value (0.4.37 ships no
    differentiation rule for the primitive, hence the custom_vjp). The
    cotangent is pinned too, so the mirrored backward dots see the same
    materialized layout."""
    return jax.lax.optimization_barrier(x)


def _pin_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _pin_bwd(_, ct):
    return (jax.lax.optimization_barrier(ct),)


_pin_buffer.defvjp(_pin_fwd, _pin_bwd)


def _ep_dispatch(x, idx, wgt, eparams, *, ep, num_experts, k,
                 capacity_factor, apply_experts, ep_axes=("ep",),
                 ep_overlap: str = "off", ep_chunks: int = 2):
    """Per-rank EP dispatch body: capacity scatter → all_to_all → local
    experts → all_to_all → weighted combine. Requires a bound manual
    ``"ep"`` axis (from ``_ep_forward``'s shard_map or the pipeline's
    manual region). ``ep_axes``: one axis name, or (outer, inner) for the
    hierarchical two-stage exchange on multi-slice meshes.

    ``ep_overlap="chunk"`` slices the capacity dim into ``ep_chunks``
    pieces and runs dispatch-a2a → FFN → combine-a2a per slice. Slices
    are disjoint and rows independent, so the re-concatenated combine
    buffer is bitwise-identical to the serialized path — but chunk
    *i+1*'s dispatch-a2a and chunk *i*'s combine-a2a share no data with
    chunk *i*'s expert matmul, so the scheduler overlaps them (the same
    no-data-dependency contract the tp/fsdp rings rely on). The backward
    inherits the chunk structure through linearization: the transpose of
    ``all_to_all`` is an ``all_to_all``, so the mirrored exchanges of
    chunk *i* overlap chunk *i±1*'s FFN backward the same way — no
    custom_vjp needed to keep the overlap shape."""

    def a2a(buf):
        if len(ep_axes) == 2:
            return hierarchical_all_to_all(buf, ep_axes[0], ep_axes[1])
        return jax.lax.all_to_all(buf, ep_axes[0], split_axis=0,
                                  concat_axis=0)

    E, El = num_experts, num_experts // ep
    T = x.shape[0]                       # local tokens
    C = max(1, math.ceil(capacity_factor * T * k / E))
    idx_f = idx.reshape(T * k)           # token-major, k inner
    oh = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)      # (Tk, E)
    pos = (jnp.cumsum(oh, axis=0) - oh)[
        jnp.arange(T * k), idx_f]        # rank within expert
    keep = (pos < C).astype(jnp.float32)
    slot = idx_f * C + jnp.clip(pos, 0, C - 1)
    disp = jax.nn.one_hot(slot, E * C, dtype=jnp.float32) \
        * keep[:, None]                  # (Tk, E*C)
    xk = jnp.repeat(x, k, axis=0)        # (Tk, d) matches idx_f
    buf = jnp.einsum("ts,td->sd", disp,
                     xk.astype(jnp.float32))   # (E*C, d)
    buf = buf.reshape(ep, El, C, -1)
    n_chunks = min(int(ep_chunks), C) if ep_overlap == "chunk" else 1
    if ep > 1:
        # analytic ledger (trace time, like the tp/fsdp rings): two
        # a2as per forward, each moving the (ep-1)/ep remote share of
        # the local capacity buffer; the backward mirrors them (a2a
        # transposes to a2a) — accounted where the bwd traces
        from hetu_tpu.parallel.overlap import record_comm_bytes
        record_comm_bytes(
            "ep_a2a",
            2 * buf.size * buf.dtype.itemsize * (ep - 1) // ep,
            overlapped=n_chunks > 1)
    if n_chunks <= 1:
        # serialized: one dispatch exchange, all experts, one combine
        buf = a2a(buf)                             # (ep, El, C, d)
        xe = jnp.swapaxes(buf, 0, 1).reshape(El, ep * C, -1)
        ye = apply_experts(eparams, xe)            # (El, ep*C, d)
        ye = jnp.swapaxes(ye.reshape(El, ep, C, -1), 0, 1)
        ye = a2a(ye)                               # (ep, El, C, d)
    else:
        # pin the dispatch buffer before slicing: otherwise XLA fuses
        # the capacity slices back into the dispatch einsum and
        # computes each row subset with its own reduction blocking —
        # 1-ulp drift vs the serialized path's single full-buffer
        # einsum. Pinned, chunks are pure memory slices.
        buf = _pin_buffer(buf)
        bounds = [i * C // n_chunks for i in range(n_chunks + 1)]
        outs = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            c = hi - lo
            bi = a2a(buf[:, :, lo:hi])             # (ep, El, c, d)
            xi = jnp.swapaxes(bi, 0, 1).reshape(El, ep * c, -1)
            yi = apply_experts(eparams, xi)
            yi = jnp.swapaxes(yi.reshape(El, ep, c, -1), 0, 1)
            outs.append(a2a(yi))
        ye = jnp.concatenate(outs, axis=2)         # (ep, El, C, d)
        # pin the re-concatenated buffer: without the barrier XLA
        # splits the combine dot across the concat (dot(disp, concat)
        # → Σ per-chunk partial dots), re-associating the s-reduction
        # by 1 ulp — the barrier makes the combine consume the same
        # materialized layout the serialized a2a output has, keeping
        # the bitwise contract while the chunk a2as still overlap
        ye = _pin_buffer(ye)
    ye = ye.reshape(E * C, -1)
    outk = jnp.einsum("ts,sd->td", disp,
                      ye.astype(jnp.float32))  # (Tk, d)
    w = (wgt.reshape(T * k) * keep)[:, None]
    return jnp.sum((outk * w).reshape(T, k, -1), axis=1)


# -- one chip's share of an expert-parallel layer (serving) -------------------
def count_local_share(sizes, *, tile: Optional[int] = None,
                      window: Optional[int] = None,
                      form: Optional[str] = None) -> None:
    """:class:`ExpertShareMoE`'s counters, on the host: ``sizes``
    ``(layer calls, held experts)`` int — the group sizes of every
    layer of one executed scan (``return_stats=True``; the serving
    engine calls this with what its step returned). With ``tile`` (and
    the ``window`` of a share) also what the grouped matmuls' aligned
    layout cost: the rows routed here beside the rows of the tiles the
    kernel visited, and with ``form`` which form of the grouped call the
    lane's layer calls made (:meth:`ExpertShareMoE.count_share` knows
    all three)."""
    from hetu_tpu import telemetry
    import numpy as np
    reg = telemetry.get_registry()
    sizes = np.asarray(sizes)
    if tile:
        from hetu_tpu.ops.grouped_matmul_pallas import grouped_rows_computed
        rows = reg.counter(
            "moe_grouped_rows_total",
            "rows of the grouped expert matmuls: live = (token, choice) "
            "pairs routed to a held expert, computed = rows of the row "
            "tiles the kernel visited (every group rounded up to the "
            "tile), summed over layer calls")
        rows.inc(float(sizes.sum()), kind="live")
        rows.inc(float(sum(grouped_rows_computed(s, tile, window)
                           for s in sizes)), kind="computed")
    if form:
        reg.counter(
            "moe_grouped_form_calls_total",
            "layer calls of an expert-share MoE layer by the form of "
            "their grouped expert call: fused = gate, up, SwiGLU and "
            "down of a (row tile, expert) in one grid step, split = "
            "three grouped matmuls").inc(float(sizes.shape[0]), form=form)
    reg.counter(
        "moe_local_calls_total",
        "executed calls of an expert-share MoE layer").inc(
            float(sizes.shape[0]))
    reg.counter(
        "moe_local_assignments_total",
        "(token, choice) pairs routed to an expert this chip holds").inc(
            float(sizes.sum()))
    reg.counter(
        "moe_local_experts_touched_total",
        "local experts that got at least one token, summed over layer "
        "calls (their weights are what a call has to read)").inc(
            float((sizes > 0).sum()))
    per = reg.counter(
        "moe_local_expert_tokens",
        "(token, choice) pairs routed to each local expert; max over "
        "mean is the share's load imbalance")
    for e, n in enumerate(sizes.sum(0).tolist()):
        if n:
            per.inc(float(n), expert=str(e))


def count_group_held(values, tokens: Optional[int] = None) -> None:
    """A group-limited :class:`ExpertShareMoE`'s counters, on the host:
    ``values`` ``(layer calls, 2)`` int — per layer of one executed
    scan, the tokens whose kept groups reach an expert held here, and
    the tokens routed (``return_stats=True``; ``tokens``, the lane's
    rows, is every ``layer_stats`` emit's and not needed here)."""
    from hetu_tpu import telemetry
    import numpy as np
    reg = telemetry.get_registry()
    held, tokens = np.asarray(values, np.int64).sum(axis=0).tolist()
    reg.counter(
        "moe_group_held_total",
        "tokens whose kept routing groups include one held on this "
        "chip, summed over layer calls").inc(float(held))
    reg.counter(
        "moe_group_tokens_total",
        "tokens routed by a group-limited expert layer, summed over "
        "layer calls").inc(float(tokens))


class ExpertShareMoE(Module):
    """A routed-expert layer that holds ``local_experts = (first,
    count)`` of ``num_experts`` SwiGLU experts — one chip's share of an
    expert-parallel deployment (default: all of them).

    The router keeps its full width: ``s = sigmoid(x W_r)``
    (``score="softmax"``: ``softmax(x W_r)`` over the experts), the ``k``
    largest are chosen and weighted ``w_e = s_e / sum_chosen s``
    (normalised over ALL k chosen, held here or not). With
    ``select_bias`` the choice is by ``s + b`` (a per-expert bias, a
    parameter) while the weights stay ``s``'s; ``scale`` multiplies
    them. With ``n_group`` > 1 the choice is GROUP-LIMITED: the experts
    stand in ``n_group`` groups of consecutive experts, a group's score
    is the sum of its two largest selection scores, only the
    ``topk_group`` best groups stay, and the ``k`` largest selection
    scores inside them are chosen (in a deployment that holds a group a
    chip, a token reaches at most ``topk_group`` chips); at 1 / 1 the
    route is the ungrouped one to the bit. The layer returns
    ``sum_{e chosen, first <= e < first + count} w_e E_e(x)`` — what the
    absent experts would have added is left out, here and in the
    reference alike (``benchmark/reference/cohere2_moe.py``). No token
    is dropped: there is no capacity. The (token, choice) pairs are
    sorted by local expert and the experts run as a grouped call over
    the sorted rows — the Pallas kernels of
    ``ops/grouped_matmul_pallas.py``: a row costs one expert's
    arithmetic, a held expert that got a row has each of its matrices
    read ONCE a call and one that got none never — never a per-token
    gather of expert weights. The sorted rows are laid out with each
    expert's on row tiles of their own (:meth:`tile_rows`, from the
    call's rows and the held experts: 16 where a lane has a few rows an
    expert, 64 or 256 where it has dozens or hundreds; the tables in the
    ``hetu.moe_route`` scope). The call takes one of two forms, from the
    shapes alone (:meth:`grouped_form`). **Fused**, where an expert's
    three matrices fit a grid step beside the lane's float32 result
    (every expert of the benchmark but Command A+'s): ONE call a layer
    call and lane whose step is gate, up, SwiGLU and down of a (row
    tile, expert) and then the weighted sum — each live row times its
    routing weight added into its token's row of the result, in the
    rows' order; no result in the aligned layout, no gather back.
    **Split**: three calls a layer call — the second writes ``silu(gate)
    * up`` as its epilogue — and the results come back through the
    layout's inverse in the one gather that brings the pairs to token
    order, to be weighted and summed over the choices. Inside a layer scan the
    expert weights come as :class:`~hetu_tpu.nn.module.StackedLeaf`
    (the model's block lists them as ``unsliced``): the kernel's index
    map takes ``layer x held + expert`` from a scalar operand — no
    layer's 1.6 GB of experts is sliced out (a copy) for it. A SHARE
    walks its sorted rows in windows (:meth:`_window_rows`), each laid
    out and run by the same kernel. ``jax.lax.ragged_dot``, which these
    calls were until PR 43, stays in the tests as the reference
    (``tests/conftest.py::ragged_dot_experts``).

    Operands: the router in float32 at the highest matmul precision on
    the input as given (a top-k flips on rounding; the input is the
    block's float32 norm), the experts in the module's compute dtype
    with float32 accumulation.
    """

    returns_aux = False

    def __init__(self, features: int, hidden: int, num_experts: int, *,
                 k: int, local_experts: Optional[tuple] = None,
                 select_bias: bool = False,
                 scale: Optional[float] = None, n_group: int = 1,
                 topk_group: int = 1, score: str = "sigmoid", init=None):
        super().__init__()
        first, count = local_experts or (0, num_experts)
        if score not in ("sigmoid", "softmax") or (
                score == "softmax" and (select_bias or n_group > 1)):
            raise ValueError(
                f"score {score!r}: sigmoid, or softmax without a "
                f"selection bias or groups")
        #: ``s``: ``sigmoid(z)`` a logit, or ``softmax(z)`` over the
        #: experts (float32) — chosen and renormalised alike
        self.score = score
        if num_experts % n_group or not 1 <= topk_group <= n_group \
                or k > topk_group * (num_experts // n_group) \
                or (n_group > 1 and num_experts // n_group < 2):
            raise ValueError(
                f"top-{k} of {topk_group} of {n_group} groups of "
                f"{num_experts} experts")
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        if not (0 <= first and count >= 1
                and first + count <= num_experts):
            raise ValueError(f"local_experts {local_experts} outside "
                             f"the {num_experts} experts")
        if k > num_experts:
            raise ValueError(f"top-{k} of {num_experts} experts")
        self.num_experts, self.k = num_experts, k
        self.local_experts = (int(first), int(count))
        #: an expert's gate and up are ``features x hidden``
        self.widths = (int(features), int(hidden))
        #: ``{pairs a call: (form, tile)}`` as each traced lane chose
        #: them — under ITS compute dtype, which the host thread that
        #: counts does not run under: the counters read the choice
        self._lanes: dict = {}
        init = init or normal_init(0.02)
        self.param("router", (features, num_experts), init,
                   axes=("embed", None))
        # bias-corrected routing (both static None / False on a model
        # that has neither): the k experts with the largest ``s + b``
        # are CHOSEN, their weights still come from ``s`` alone, times
        # ``scale``. The bias is drawn like a weight, not zeros: a
        # program that leaves it out must differ
        self.select_bias, self.scale = bool(select_bias), scale
        if select_bias:
            self.param("select_bias", (num_experts,), init, axes=(None,))
        self.param("wg", (count, features, hidden), init,
                   axes=("expert", "embed", "mlp"))
        self.param("wi", (count, features, hidden), init,
                   axes=("expert", "embed", "mlp"))
        self.param("wo", (count, hidden, features), init,
                   axes=("expert", "mlp", "embed"))

    def _window_rows(self, pairs: int) -> int:
        """Sorted rows a call of the grouped matmuls takes: all the
        ``pairs`` where every expert is held; for a share twice the
        pairs it expects (``pairs x count / num_experts``), in whole
        lane tiles."""
        count = self.local_experts[1]
        if count == self.num_experts:
            return pairs
        want = max(2 * pairs * count // self.num_experts, 128)
        return min(pairs, -(-want // 128) * 128)

    def grouped_form(self, pairs: int) -> str:
        """``"fused"`` where an expert's three matrices and the lane's
        float32 result fit one call of
        ``ops.grouped_matmul_pallas.grouped_swiglu`` (from the widths,
        the compute dtype and the tokens of a call that routes
        ``pairs`` pairs), else ``"split"``: three ``grouped_matmul``
        calls and the gather back."""
        from hetu_tpu.ops.grouped_matmul_pallas import grouped_swiglu_fits
        fits = grouped_swiglu_fits(
            *self.widths, pairs // self.k,
            jnp.dtype(self.compute_dtype()).itemsize)
        return "fused" if fits else "split"

    def tile_rows(self, pairs: int) -> int:
        """Rows of a tile of the grouped call where a call routes
        ``pairs`` (token, choice) pairs
        (``ops.grouped_matmul_pallas.grouped_tile_rows`` of the held
        experts and the call's rows): the split form's from the
        window's rows; a share's fused form's from one and a half times
        the rows its experts EXPECT (three quarters of the window, which
        is twice them) — an expert's busiest-over-mean is 1.1-1.4, the
        fused step costs its rows and a second tile of an expert
        fetches nothing again (the chip's sweep: PERF.md, PR 62)."""
        from hetu_tpu.ops.grouped_matmul_pallas import grouped_tile_rows
        rows, count = self._window_rows(pairs), self.local_experts[1]
        if count < self.num_experts \
                and self.grouped_form(pairs) == "fused":
            rows = 3 * rows // 4
        return grouped_tile_rows(rows, count)

    @property
    def layer_stats(self) -> dict:
        """What ``return_stats=True`` reports, as the ``layer_stats``
        entries ``{name: (shape, dtype, emit)}`` of a block."""
        out = {"sizes": ((self.local_experts[1],), jnp.int32,
                         self.count_share)}
        if self.n_group > 1:
            out["group_held"] = ((2,), jnp.int32, count_group_held)
        return out

    def count_share(self, sizes, tokens: Optional[int] = None) -> None:
        """The layer's host counters (a block's ``layer_stats`` emit):
        :func:`count_local_share` of the scan's group sizes; the lane's
        ``tokens`` a call say which window the calls used and which
        form and tile the lane's trace chose."""
        pairs = (tokens or 0) * self.k
        if not pairs:
            return count_local_share(sizes)
        form, tile = self._lanes.get(pairs) or (
            self.grouped_form(pairs), self.tile_rows(pairs))
        count_local_share(sizes, tile=tile, form=form,
                          window=self._window_rows(pairs))

    def _kept_groups(self, sel):
        """``sel (T, E)`` selection scores -> ``(T, n_group)`` bool: the
        ``topk_group`` groups with the largest sum of their two best."""
        per = sel.reshape(sel.shape[0], self.n_group, -1)
        score = jax.lax.top_k(per, 2)[0].sum(-1)
        _, best = jax.lax.top_k(score, self.topk_group)
        return jnp.any(
            best[:, :, None] == jnp.arange(self.n_group)[None, None, :],
            axis=1)

    def route(self, params, x, *, return_kept: bool = False):
        """x (T, d) -> (experts (T, k) int32, weights (T, k) float32)
        [, the kept groups (T, n_group) bool]."""
        z = jnp.matmul(x.astype(jnp.float32),
                       params["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        kept = jnp.ones((x.shape[0], 1), bool)
        if self.n_group > 1:
            s = jax.nn.sigmoid(z)
            sel = s + params["select_bias"].astype(jnp.float32) \
                if self.select_bias else s
            kept = self._kept_groups(sel)
            _, idx = jax.lax.top_k(jnp.where(
                jnp.repeat(kept, self.num_experts // self.n_group, axis=1),
                sel, -jnp.inf), self.k)
            top = jnp.take_along_axis(s, idx, axis=-1)
        elif self.select_bias:
            s = jax.nn.sigmoid(z)
            _, idx = jax.lax.top_k(
                s + params["select_bias"].astype(jnp.float32), self.k)
            top = jnp.take_along_axis(s, idx, axis=-1)
        elif self.score == "softmax":
            top, idx = jax.lax.top_k(jax.nn.softmax(z, axis=-1), self.k)
        else:
            top, idx = jax.lax.top_k(jax.nn.sigmoid(z), self.k)
        w = top / top.sum(-1, keepdims=True)
        if self.scale is not None:
            w = w * self.scale
        if return_kept:
            return idx.astype(jnp.int32), w, kept
        return idx.astype(jnp.int32), w

    def __call__(self, params, x, *, return_stats: bool = False):
        """``return_stats``: ``(out, {"sizes": ...})`` — also the
        ``(count,)`` int32 numbers of (token, choice) pairs each held
        expert got — and, under a group limit, ``"group_held": (2,)
        int32`` beside them: the tokens whose kept groups hold an
        expert held here, and the tokens routed."""
        dt = self.compute_dtype()
        d = x.shape[-1]
        xf = x.reshape(-1, d)
        k = self.k
        M = xf.shape[0] * k
        first, count = self.local_experts
        R = self._window_rows(M)
        tile = self.tile_rows(M)
        form = self.grouped_form(M)
        fused = form == "fused"
        self._lanes[M] = (form, tile)
        with jax.named_scope("hetu.moe_route"):
            idx, w, kept = self.route(params, xf, return_kept=True)
            local = idx - first
            mine = (local >= 0) & (local < count)
            # pairs held elsewhere sort behind every local group
            key = jnp.where(mine, local, count).reshape(M)
            order = jnp.argsort(key, stable=True)
            sizes = jnp.sum(
                key[:, None] == jnp.arange(count, dtype=key.dtype)[None],
                axis=0, dtype=jnp.int32)
            # where each pair's row went, to bring its result back (the
            # fused form adds its rows into their tokens' and has no way
            # back to walk)
            back = None if fused else jnp.zeros((M,), jnp.int32) \
                .at[order].set(jnp.arange(M, dtype=jnp.int32))
            total = sizes.sum()

        from hetu_tpu.ops.grouped_matmul_pallas import (
            grouped_combine, grouped_layout, grouped_matmul, grouped_swiglu,
        )

        def leaf(name):
            w, layer = params[name], None
            if isinstance(w, StackedLeaf):
                w, layer = w
            return w.astype(dt), layer

        def grouped(a, name, lay, **kw):
            w, layer = leaf(name)
            return grouped_matmul(a, w, lay, layer=layer, **kw)

        def experts(at, sizes, wanted):
            """The split form: the sorted pairs ``at`` (indices into the
            pairs; the first ``sizes.sum()`` are live) through their
            experts, in the ALIGNED layout, and back: row ``i`` of the
            result is the sorted row ``wanted[i]``'s. Rows of no group
            are never computed and never read."""
            with jax.named_scope("hetu.moe_route"):
                lay = grouped_layout(sizes, rows=at.shape[0], tile=tile)
                rows = jnp.take(xd, jnp.take(at, lay.src) // k, axis=0)
                wanted = jnp.take(lay.dst, wanted)
            h = grouped(rows, "wi", lay, gate=grouped(rows, "wg", lay),
                        out_dtype=dt)
            return jnp.take(grouped(h, "wo", lay), wanted, axis=0)

        def fused_experts(at, sizes):
            """The fused form: the sorted pairs ``at`` through their
            experts in the aligned layout, every live row weighted and
            added into its token's row — ``(tokens, d)`` float32."""
            with jax.named_scope("hetu.moe_route"):
                lay = grouped_layout(sizes, rows=at.shape[0], tile=tile)
                pair = jnp.take(at, lay.src)
                rows = jnp.take(xd, pair // k, axis=0)
                combine = grouped_combine(lay, sizes, pair // k,
                                          jnp.take(w_pairs, pair))
            (wg, layer), (wi, _), (wo, _) = map(leaf, ("wg", "wi", "wo"))
            return grouped_swiglu(rows, wg, wi, wo, lay, combine,
                                  tokens=xf.shape[0], layer=layer)

        w_pairs = w.reshape(M)
        with jax.named_scope("hetu.moe_route"):
            # the tokens in the operands' dtype once: the padded layout
            # gathers more rows than there are pairs
            xd = xf.astype(dt)
        with jax.named_scope("hetu.moe_experts"):
            if R >= M and fused:
                out = fused_experts(order, sizes)
            elif R >= M:
                out = jnp.where(
                    (back < total)[:, None],
                    experts(order, sizes, back) * w_pairs[:, None],
                    0.0).reshape(-1, k, d).sum(1)
            else:
                # a SHARE: the pairs held here are the first ``total``
                # sorted rows, an eighth of them or so — the grouped
                # matmuls walk them in windows of R rows (one window,
                # unless the tokens crowd onto this chip), never the
                # rows behind them
                order_p = jnp.pad(order, (0, -M % R))
                hi = jnp.cumsum(sizes)
                lo = hi - sizes

                def window(i, out):
                    start = i * R
                    inside = jnp.clip(jnp.minimum(hi, start + R)
                                      - jnp.maximum(lo, start), 0)
                    if fused:
                        return out + fused_experts(jax.lax.dynamic_slice(
                            order_p, (start,), (R,)), inside)
                    rel = back - start
                    mine_w = (rel >= 0) & (rel < R) & (back < total)
                    part = experts(
                        jax.lax.dynamic_slice(order_p, (start,), (R,)),
                        inside, jnp.clip(rel, 0, R - 1)) * w_pairs[:, None]
                    return out + jnp.where(mine_w[:, None], part, 0.0) \
                        .reshape(-1, k, d).sum(1)

                out = jax.lax.fori_loop(
                    0, (total + R - 1) // R, window,
                    jnp.zeros((xf.shape[0], d), jnp.float32))
        out = out.astype(dt).reshape(x.shape)
        if not return_stats:
            return out
        stats = {"sizes": sizes}
        if self.n_group > 1:
            with jax.named_scope("hetu.moe_route"):
                # the groups that overlap the experts held here
                per = self.num_experts // kept.shape[1]
                g = jnp.arange(kept.shape[1])
                mine_g = (g * per < first + count) & ((g + 1) * per > first)
                held = jnp.sum(jnp.any(kept & mine_g[None], axis=1),
                               dtype=jnp.int32)
            stats["group_held"] = jnp.stack(
                [held, jnp.asarray(xf.shape[0], jnp.int32)])
        return out, stats
