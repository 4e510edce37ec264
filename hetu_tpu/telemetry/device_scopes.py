"""Device scopes: the program's own names for what runs on the chip.

A device trace (``jax.profiler``) names each event by its HLO
instruction — ``fusion.740``, ``closed_call.35`` — and carries no
``metadata``. The optimized HLO of the executable does: every instruction
keeps ``metadata={op_name="jit(step)/jvp(hetu.loss)/..."}``, the JAX name
stack at the point that emitted it, and its instruction names are the
ones the trace shows. So a ``jax.named_scope("hetu.<what>")`` placed
where the work happens can be read back after the fact by joining the
trace's instruction names with :func:`scopes_of` on the executable's
text. The scopes are metadata only: they do not change compiled code.

The vocabulary (all start ``hetu.``; ``docs/OBSERVABILITY.md``):

====================  ================================================
``hetu.loss``         the differentiated loss of a train step
``hetu.opt``          grad norm, clip, optimizer update, apply
``hetu.flash_fwd``    the flash forward kernel: ONE Pallas call a layer
                      (``hetu_flash_fwd``; its key tiles are an in-kernel
                      loop over a resident K / V, not grid steps)
``hetu.flash_bwd``    the flash backward kernels: TWO Pallas calls a layer
                      (``hetu_flash_bwd_dq``, ``hetu_flash_bwd_dkv``)
``hetu.paged_attn``   the paged decode attention kernel
``hetu.fused_ce``     the fused LM-head cross-entropy kernels
``hetu.prefill_lane`` the fused serving step's packed prefill lane
``hetu.decode_lane``  the fused serving step's decode/verify lane
``hetu.kv_arena``     KV arena writes (paged scatters, CoW copies)
``hetu.sample``       sampling: logits adjustment, draws, verify
``hetu.diffusion_sample`` the block lane's sampler: confidence, choice, transfer
``hetu.moe_route``    expert-share MoE: router, top-k, sort, row gather
``hetu.moe_experts``  expert-share MoE: grouped matmuls, weighting, unsort
``hetu.moe_shared``   the shared experts' gated MLP (averaged or summed)
``hetu.mla_down``     latent attention: down-projection, latent norm, RoPE key
``hetu.mla_absorb``   latent attention: q through W_uk, results through W_uv
``hetu.mla_expand``   latent attention: per-head K and V from the latent rows
``hetu.retention_scan``   power retention: a prefill pack's chunk form (one kernel)
``hetu.retention_update`` power retention: the decode rows' update in place
``hetu.ssm_conv``     selective scan (Mamba): the short convolution and its tails
``hetu.ssm_scan``     selective scan: a prefill pack's tokens (one kernel)
``hetu.ssm_update``   selective scan: the decode rows' update in place
``hetu.gdn_conv``     Gated DeltaNet: the short convolution and its tails
``hetu.gdn_scan``     Gated DeltaNet: a prefill pack's chunk form (the KDA kernel)
``hetu.gdn_update``   Gated DeltaNet: the decode rows' update in place
``hetu.gated_attn``   gated softmax attention: the whole mixer of such a layer
====================  ================================================

The rule (:func:`classify`): an instruction belongs to the INNERMOST
``hetu.*`` component of its ``op_name`` (components come wrapped —
``jvp(hetu.loss)``, ``transpose(jvp(hetu.loss))`` — the wrappers are
looked into); under ``hetu.loss`` it is ``bwd`` when the path holds
``transpose(`` or a remat recomputation (``rematted_computation``), else
``fwd`` (a flash kernel whose name lost the loss's wrapper is in the pass
its own name says); a fusion without metadata of its own is its root's;
an instruction the COMPILER made from a program's op and named after
itself (``op_name="ragged-dot-none"``: no name stack at all;
``COMPILER_NAMED`` lists them) is its first consumer's that has a
scope; no ``hetu.`` component at all is
``unscoped``.

Steps make themselves readable through :func:`register_step` when they
are first built (``engine.precompile`` for the AOT train step, the
serving engine at its first dispatch). Registration stores a thunk and
nothing else: no HLO is fetched, compiled or parsed until someone calls
:func:`registered_scopes` — the benchmark's traced run does, after its
window.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import threading
from typing import Callable, Optional

UNSCOPED = "unscoped"
VOCABULARY = (
    "hetu.loss", "hetu.opt", "hetu.flash_fwd", "hetu.flash_bwd",
    "hetu.paged_attn", "hetu.fused_ce", "hetu.prefill_lane",
    "hetu.decode_lane", "hetu.kv_arena", "hetu.sample",
    "hetu.moe_route", "hetu.moe_experts", "hetu.moe_shared",
    "hetu.mla_down", "hetu.mla_absorb", "hetu.mla_expand",
    "hetu.diffusion_sample", "hetu.retention_scan",
    "hetu.retention_update", "hetu.ssm_conv", "hetu.ssm_scan",
    "hetu.ssm_update", "hetu.gdn_conv", "hetu.gdn_scan",
    "hetu.gdn_update", "hetu.gated_attn",
)

#: ``op_name``s the TPU compiler gives an op it made from a program's
#: op, in place of the name stack (listed, not guessed: parameters and
#: ``reduce_sum`` carry a bare name too, and stay ``unscoped``)
COMPILER_NAMED = ("ragged-dot",)

_SCOPE = re.compile(r"hetu\.[a-z_0-9]+")
_KERNEL_PHASE = {"hetu.flash_fwd": "fwd", "hetu.flash_bwd": "bwd"}
# `  ROOT %fusion.3 = f32[8]{0} fusion(...), ..., metadata={...}`
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
# `%fused_computation.3 (p0: f32[8]) -> f32[8] {` / `ENTRY %main.7 (...`
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
#: registered steps kept (oldest dropped): a thunk pins its executable
MAX_REGISTERED = 32


@dataclasses.dataclass(frozen=True)
class Scope:
    """Where one HLO instruction belongs."""

    scope: str              # innermost hetu.* component, or "unscoped"
    path: tuple             # every hetu.* component, outermost first
    phase: Optional[str]    # "fwd" | "bwd" under hetu.loss, else None
    remat: bool             # a recomputation replayed in the backward

    @property
    def label(self) -> str:
        """``hetu.loss.fwd`` / ``hetu.loss.bwd`` for the loss's own
        instructions, the scope's name otherwise."""
        if self.scope == "hetu.loss" and self.phase:
            return f"{self.scope}.{self.phase}"
        return self.scope


_NONE = Scope(UNSCOPED, (), None, False)


def classify(op_name: str) -> Scope:
    """The scope of one ``op_name`` by the rule in the module docstring."""
    path = []
    for name in _SCOPE.findall(op_name):
        if not path or path[-1] != name:
            path.append(name)
    if not path:
        return _NONE
    remat = "rematted_computation" in op_name
    phase = None
    if "hetu.loss" in path:
        phase = "bwd" if remat or "transpose(" in op_name else "fwd"
    elif path[-1] in _KERNEL_PHASE:
        # inside a custom_vjp rule's own jaxpr the outer name stack can
        # be missing ("checkpoint/hetu.flash_bwd/reduce_sum"): the
        # kernel's name says which pass it is
        phase = "bwd" if remat else _KERNEL_PHASE[path[-1]]
    return Scope(path[-1], tuple(path), phase, remat)


def describe(hlo_text: str) -> dict[str, Scope]:
    """``{instruction name: Scope}`` for every instruction of an
    optimized HLO module's text (``compiled.as_text()``)."""
    op_names: dict[str, str] = {}
    users: dict[str, list] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}       # computation -> its ROOT instruction
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and "->" in line:
            computation = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        is_root, name, rest = m.groups()
        if is_root and computation is not None:
            roots[computation] = name
        for operand in _OPERAND.findall(rest):
            users.setdefault(operand, []).append(name)
        op = _OP_NAME.search(rest)
        if op is not None:
            op_names[name] = op.group(1)
        else:
            op_names.setdefault(name, "")
            called = _CALLS.search(rest)
            if called is not None:
                calls[name] = called.group(1)
    def consumers_op(name: str, depth: int) -> str:
        """The first consumer's op_name that has a scope, looking
        through consumers that have no metadata at all (a
        get-tuple-element)."""
        for user in users.get(name, ()):
            found = op_of(user, depth + 1)
            if not found and depth < 8:
                found = consumers_op(user, depth + 1)
            if "hetu." in found:
                return found
        return ""

    def op_of(name: str, depth: int = 0) -> str:
        op = op_names.get(name, "")
        if depth > 8:
            return op
        if op.startswith(COMPILER_NAMED):
            # the compiler's own op, named after itself: its consumer's
            return consumers_op(name, depth) or op
        if op:
            return op
        # a fusion without metadata of its own is its root's
        root = roots.get(calls.get(name, ""))
        return op_of(root, depth + 1) if root else ""

    return {name: classify(op_of(name)) for name in op_names}


def scopes_of(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope}`` — :attr:`Scope.label` of each
    instruction: a vocabulary name, ``hetu.loss.fwd`` / ``hetu.loss.bwd``
    or ``unscoped``."""
    return {k: v.label for k, v in describe(hlo_text).items()}


# -- the process-global registration ---------------------------------------
_LOCK = threading.Lock()
_STEPS: "collections.OrderedDict[tuple, Callable[[], Optional[str]]]" = \
    collections.OrderedDict()
_PARSED: dict[tuple, dict[str, Scope]] = {}


def register_step(what: str, thunk: Callable[[], Optional[str]], *,
                  key: object = None) -> None:
    """Make a compiled step readable: ``thunk()`` returns its optimized
    HLO text (or ``None`` when the executable is gone). Called when the
    step is first built; stores the thunk and does nothing else.
    ``key`` tells apart several steps of one kind (the strategies of a
    hot-switching trainer); the newest registration under one
    ``(what, key)`` wins."""
    k = (what, key)
    with _LOCK:
        _STEPS.pop(k, None)
        _PARSED.pop(k, None)
        _STEPS[k] = thunk
        while len(_STEPS) > MAX_REGISTERED:
            old, _ = _STEPS.popitem(last=False)
            _PARSED.pop(old, None)


def registered_steps() -> list[tuple]:
    with _LOCK:
        return list(_STEPS)


def registered_scopes() -> dict[tuple, dict[str, Scope]]:
    """``{(what, key): {instruction name: Scope}}`` for every registered
    step whose text can still be had. THIS is where HLO is fetched
    (compiled, for a jit-dispatched step) and parsed — call it after the
    work that is being measured, never inside it."""
    with _LOCK:
        todo = [(k, t) for k, t in _STEPS.items() if k not in _PARSED]
    for k, thunk in todo:
        text = thunk()
        if text:
            with _LOCK:
                if k in _STEPS:
                    _PARSED[k] = describe(text)
    with _LOCK:
        return {k: _PARSED[k] for k in _STEPS if k in _PARSED}


def clear_registered() -> None:
    with _LOCK:
        _STEPS.clear()
        _PARSED.clear()
