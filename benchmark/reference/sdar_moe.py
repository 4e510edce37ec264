"""SDAR-MoE (``sdar_moe``) in plain ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, explicit masks, no
kernels, no cache. It follows the published ``config.json`` of
``JetLM/SDAR-30B-A3B-Chat`` (``config`` below is that file's keys plus
the configuration file's ``assumed`` generation settings) and the
family's ``generate.py::block_diffusion_generate``, and shares no code
with ``hetu_tpu``: it only READS the same parameter tree —
``wte.weight`` and ``lm_head.weight`` ``(V, E)``; ``blocks`` stacked
over layers on axis 0: ``norm1.scale``, ``norm2.scale``,
``attn.{q,k,v,out}_proj.weight`` ``(in, out)``, ``attn.q_gain`` /
``attn.k_gain`` ``(head_dim,)``, ``moe.router`` ``(E, experts)``,
``moe.{wg,wi}`` ``(held, E, width)``, ``moe.wo`` ``(held, width, E)``;
``final_norm.scale``.

Per layer, ``n`` = RMSNorm (eps ``rms_norm_eps``, a gain):
``h = x + Attn(n1(x))``, ``y = h + MoE(n2(h))``.

* Attention: GQA; RMSNorm over each head's ``head_dim`` numbers on q
  and on k (gains shared by the heads), then RoPE on all dims in split
  halves (dim ``i`` pairs with ``i + d/2``, angle ``p theta**(-2i/d)``),
  scores ``q k / sqrt(head_dim)``, softmax in float32. A query at
  position ``p`` sees key ``j`` iff ``j // B <= p // B``
  (``B = block_length``): its own block whole and every block before.
* MoE: ``pi = softmax(u W_r)`` over all experts, the
  ``num_experts_per_tok`` largest chosen, ``w_e = pi_e / sum_chosen
  pi``; expert ``E_e(u) = (silu(u W_g) * (u W_u)) W_d``. No shared
  expert. ``local_experts = (first, count)``: the tree holds only those
  experts, routing stays over all of them, and only the held experts'
  terms are summed — what the absent ones would add is left out, as in
  the program.
* Logits ``n_f(h) W_head^T``; position ``i``'s logits predict position
  ``i`` (no shift).

Generation (:func:`block_diffusion_generate`): the prompt's whole
blocks are context; the first generated block starts as the prompt's
tail followed by masks, every later one as ``B`` masks. A denoise pass
runs the block's current tokens against the CLEAN keys of the blocks
before it and its OWN keys, takes ``x = argmax`` and ``c = softmax[x]``
at the masked positions (the mask id's logit at ``-inf``) and unmasks
the ``n`` masked positions of largest ``c`` (``low_confidence_static``;
``n = B // steps``, the remainder on the first passes), or every masked
position with ``c > threshold`` if those are at least ``n``
(``low_confidence_dynamic``). With no cache a commit pass is nothing to
compute: the next block's passes see the finished block's clean keys.

:func:`streams` is the same arithmetic for a whole finished request at
once (the benchmark's comparison): one clean stream and, per pass, one
noised stream whose queries see the clean keys of earlier blocks and
the noised keys of their own block.

Departures from the published description (each in the configuration
file's ``assumed``): ``block_length``, ``denoising_steps``, no logit
shift, the mask id, q/k norm as Qwen3-MoE's, split-half RoPE, ties of
confidence to the lower position.

The keyword arguments after ``*`` named ``intra``, ``keys_from``,
``order``, ``score``, ``qk_norm`` and ``operands`` exist for ONE
reading each: a reference changed in that one way, which the
benchmark's comparison has to refuse (``PERF.md`` section 6, PR 45).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
#: the ONE change of a negative control, planted by whoever reads one
#: (``streams``' and ``pick``'s keywords of the module docstring); empty
#: in every run of the benchmark
CONTROL: dict = {}


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(a, w, operands=None):
    """``a @ w`` in float32, from operands rounded to ``operands``."""
    if operands is None:
        return a @ _f32(w)
    return _f32(a.astype(operands)) @ _f32(jnp.asarray(w, operands))


def _norm(scale, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def rope_halves(x, positions, theta: float):
    """``x`` ``(seq, heads, d)``: dim ``i`` pairs with ``i + d/2``,
    rotated by ``positions * theta ** (-2i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(q, k, v, seen, *, rows: int = 256):
    """GQA attention under an explicit mask: ``q`` ``(Q, hq, d)``,
    ``k``/``v`` ``(K, hkv, d)``, ``seen`` ``(Q, K)`` bool; ``rows``
    queries at a time (the scores of a whole row would not fit beside
    the weights)."""
    Q, hq, d = q.shape
    hkv = k.shape[1]
    rows = min(rows, Q)
    pad = -Q % rows
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, rows, hkv, hq // hkv, d) / jnp.sqrt(float(d))
    sp = jnp.pad(seen, ((0, pad), (0, 0))).reshape(-1, rows, seen.shape[1])

    def some(args):
        qi, si = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k)
        s = jnp.where(si[None, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(rows, hq * d)

    return jax.lax.map(some, (qp, sp)).reshape(-1, hq * d)[:Q]


def route(router, u, config, local_experts=None, score="softmax"):
    """``(experts (S, k), weights (S, k), margin (S,))``: the chosen
    experts, their weights, and how far the nearest HELD expert's router
    logit lies from the cut between chosen and not chosen (midway
    between the k-th and the k+1-th logit), as a share of the token's
    spread of router logits: the set of chosen held experts changes on
    rounding only if one of them crosses the cut."""
    k = config["num_experts_per_tok"]
    z = u @ _f32(router)
    first, count = local_experts or (0, z.shape[-1])
    pi = jax.nn.softmax(z, axis=-1) if score == "softmax" \
        else jax.nn.sigmoid(z)
    top, idx = jax.lax.top_k(pi, k)
    zs = jax.lax.top_k(z, k + 1)[0]
    cut = (zs[:, k - 1] + zs[:, k]) / 2
    margin = jnp.abs(z[:, first:first + count] - cut[:, None]).min(-1)
    return idx, top / top.sum(-1, keepdims=True), margin / z.std(-1)


def moe(blk, u, config, local_experts=None, operands=None,
        score="softmax"):
    """The held experts' part of the routed sum on ``u = n2(h)`` ``(S,
    E)``, one expert after another, and the routing margin a token."""
    first, count = local_experts or (0, config["num_experts"])
    idx, w, margin = route(blk["moe"]["router"], u, config, local_experts,
                           score)

    def expert(e, acc):
        h = jax.nn.silu(_mm(u, blk["moe"]["wg"][e], operands)) \
            * _mm(u, blk["moe"]["wi"][e], operands)
        w_e = jnp.where(idx == first + e, w, 0.0).sum(-1)
        return acc + w_e[:, None] * _mm(h, blk["moe"]["wo"][e], operands)

    return jax.lax.fori_loop(0, count, expert, jnp.zeros_like(u)), margin


def streams(params, clean, noised, config, *, start: int = 0,
            local_experts=None, attn_rows: int = 256, intra="block",
            keys_from="clean", score="softmax", qk_norm=True,
            operands=None):
    """Final-norm hidden states of ONE clean row ``clean (S,)`` and of
    the noised rows ``noised (N, S - start)`` (positions ``start ..
    S - 1``; ``start`` a whole number of blocks: before it nothing is
    ever noised): ``(clean (S, E), noised (N, S - start, E), margin
    (N, S - start))`` — the margin the smallest routing margin over the
    layers.

    A clean query sees the clean keys of its own block and of the
    blocks before; a noised query the clean keys of the blocks before
    its own and the noised keys (its row's) of its own block."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps, theta = (config["head_dim"], config["rms_norm_eps"],
                     config["rope_theta"])
    B = config["block_length"]
    S, (N, T) = clean.shape[0], noised.shape
    pos = jnp.arange(S)
    blk_of = pos // B
    own = blk_of[:, None] == blk_of[None, :]
    # ``intra``: which keys of its OWN block a query sees ("block": all
    # of them — the model's mask; "causal" and "none" are the controls)
    inside = {"block": own,
              "causal": own & (pos[None, :] <= pos[:, None]),
              "none": jnp.zeros_like(own)}[intra]
    before = blk_of[None, :] < blk_of[:, None]
    seen_clean = before | inside                                 # (S, S)
    seen_noised = jnp.concatenate(
        [before[start:], inside[start:, start:]], axis=1)   # (T, S + T)

    def qkv(a, u, p):
        n = u.shape[0]
        q = _mm(u, a["q_proj"]["weight"], operands).reshape(n, hq, d)
        k = _mm(u, a["k_proj"]["weight"], operands).reshape(n, hkv, d)
        v = _mm(u, a["v_proj"]["weight"], operands).reshape(n, hkv, d)
        if qk_norm:
            q, k = _norm(a["q_gain"], q, eps), _norm(a["k_gain"], k, eps)
        q, k = rope_halves(q, p, theta), rope_halves(k, p, theta)
        if operands is not None:            # as a cache would store them
            q, k, v = (_f32(t.astype(operands)) for t in (q, k, v))
        return q, k, v

    def layer(carry, blk):
        xc, xn, low = carry
        a = blk["attn"]
        qc, kc, vc = qkv(a, _norm(blk["norm1"]["scale"], xc, eps), pos)
        un = _norm(blk["norm1"]["scale"], xn, eps)
        qn, kn, vn = jax.vmap(lambda u: qkv(a, u, pos[start:]))(un)
        kh, vh = kc, vc
        if keys_from == "last_denoise":
            # the control "no commit pass": the earlier blocks' keys
            # are the last denoise pass's, not the final tokens'
            kh = kc.at[start:].set(kn[-1])
            vh = vc.at[start:].set(vn[-1])
        att_c = attention(qc, kh, vh, seen_clean, rows=attn_rows)
        att_n = jax.vmap(lambda q, k, v: attention(
            q, jnp.concatenate([kh, k]), jnp.concatenate([vh, v]),
            seen_noised, rows=attn_rows))(qn, kn, vn)
        xc = xc + _mm(att_c, a["out_proj"]["weight"], operands)
        xn = xn + _mm(att_n, a["out_proj"]["weight"], operands)
        fc, _ = moe(blk, _norm(blk["norm2"]["scale"], xc, eps), config,
                    local_experts, operands, score)
        fn, margin = moe(
            blk, _norm(blk["norm2"]["scale"], xn, eps).reshape(N * T, -1),
            config, local_experts, operands, score)
        return (xc + fc, xn + fn.reshape(N, T, -1),
                jnp.minimum(low, margin.reshape(N, T))), None

    with jax.default_matmul_precision("highest"):
        emb = params["wte"]["weight"]
        (xc, xn, low), _ = jax.lax.scan(
            layer, (_f32(emb[clean]), _f32(emb[noised]),
                    jnp.full((N, T), jnp.inf)), params["blocks"])
        scale = params["final_norm"]["scale"]
        return _norm(scale, xc, eps), _norm(scale, xn, eps), low


def logits(params, input_ids, config, **kw):
    """Block-causal logits ``(rows, seq, vocab)`` in float32 of whole
    rows (every row its own clean stream)."""
    def row(ids):
        # (the noised row is the clean one again: nothing is noised)
        h, _, _ = streams(params, ids, ids[None], config, **kw)
        return h
    with jax.default_matmul_precision("highest"):
        h = jax.lax.map(row, jnp.asarray(input_ids, jnp.int32))
        return h @ _f32(params["lm_head"]["weight"]).T


def confidences(lg, mask_id: int):
    """``(x (.., ), c (..,))``: the top token and its softmax share at
    every position, the mask id's logit held at ``-inf``."""
    lg = jnp.asarray(lg, jnp.float32).at[..., mask_id].set(-jnp.inf)
    return jnp.argmax(lg, -1), jnp.exp(lg.max(-1)
                                       - jax.nn.logsumexp(lg, axis=-1))


def transfer_counts(B: int, steps: int) -> list:
    """Positions a pass unmasks: ``B // steps``, the remainder on the
    first passes."""
    return [B // steps + (i < B % steps) for i in range(steps)]


def pick(conf, masked, n: int, remasking: str, threshold: float,
         order="confidence"):
    """Which masked positions of ONE block a pass unmasks (bool
    ``(B,)``; numpy). ``order="left_to_right"`` is the control."""
    conf = np.where(masked, conf, -np.inf)
    if order == "left_to_right":
        conf = np.where(masked, -np.arange(len(conf), dtype=float), -np.inf)
    high = masked & (conf > threshold)
    if remasking == "low_confidence_dynamic" and high.sum() >= n:
        return high
    # the n largest, ties to the lower position
    top = np.argsort(-conf, kind="stable")[:n]
    out = np.zeros_like(masked)
    out[top] = True
    return out & masked


def block_diffusion_generate(params, prompt, config, *, max_tokens: int,
                             denoising_steps=None, remasking=None,
                             confidence_threshold=None, eos_id=None,
                             local_experts=None):
    """``(tokens, unmask_pass)`` of one request, greedy: the output
    tokens (the last block generated whole and cut at ``max_tokens``,
    or after ``eos_id``) and for each the pass of its block at which it
    was unmasked. No cache: each pass is one block-causal forward over
    the committed tokens and the block."""
    B, M = config["block_length"], config["mask_token_id"]
    steps = denoising_steps or config["denoising_steps"]
    remasking = remasking or config["remasking"]
    threshold = config["confidence_threshold"] \
        if confidence_threshold is None else confidence_threshold
    counts = transfer_counts(B, steps)
    prompt = np.asarray(prompt, np.int32)
    P = len(prompt)
    pos = P // B * B
    seq = list(prompt[:pos])
    block = np.full(B, M, np.int32)
    block[:P - pos] = prompt[pos:]
    masked = np.arange(B) >= P - pos
    fwd = jax.jit(lambda ids: logits(params, ids[None], config,
                                     local_experts=local_experts)[0])
    out, at = [], []
    while True:
        when = np.zeros(B, np.int64)
        for s in range(steps + 1):
            if not masked.any():
                break                       # the commit pass: no logits
            lg = np.asarray(fwd(np.asarray(seq + list(block),
                                           np.int32)))[-B:]
            x, c = (np.asarray(t) for t in confidences(lg, M))
            take = pick(c, masked, counts[s], remasking, threshold)
            block = np.where(take, x, block).astype(np.int32)
            when[take] = s
            masked = masked & ~take
        skip = max(0, P - len(seq))         # the prompt's tail: no output
        for t, w in zip(block[skip:], when[skip:]):
            if len(out) < max_tokens:
                out.append(int(t))
                at.append(int(w))
                if eos_id is not None and t == eos_id:
                    return out, at
        if len(out) >= max_tokens:
            return out, at
        seq += list(block)
        block, masked = np.full(B, M, np.int32), np.ones(B, bool)
