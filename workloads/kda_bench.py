"""The delta-rule scan of the prefill lane ALONE: one layer call of
``KimiDeltaAttention``'s ``hetu.kda_scan`` scope at Ling's prefill shape
(a pack of 2,048 tokens, 32 heads of 128, the stacked state leaf of 10
layers x 72 slots), the ``jax.numpy`` chunk form (``ops.kda.kda_scan``,
the parent's path and the kernel's oracle) beside the Pallas kernel
(``ops.kda_pallas.hetu_kda_scan``) at each head block — ms a call, and
the two forms' largest difference on the same operands (``PERF.md``
section 6, PR 46, has the chip's table).

    chiprun -- python workloads/kda_bench.py [--heads 2 4 8] [pack ...]
    python workloads/kda_bench.py --aot          # compile for a v5e, no chip

``--update``: the decode rows' update ALONE instead (the
``hetu.kda_update`` scope: one token a slot, 72 slots of which 64 are
live and one fresh) — ``ops.kda.kda_update``, a gather, the formula and
a scatter, beside ``ops.kda_pallas.hetu_kda_update`` at each head block
(``PERF.md`` section 6, PR 54); with ``--aot`` it compiles that.

``--dots``: the float32 highest-precision dot inside a kernel alone —
``(64, 128) @ (128, 128)`` a head in a loop, and batched over heads.
``--profile``: every form's device time by instruction (the kernel's
own time; of the ``jax.numpy`` form the piece loop's ``while`` against
the chunk-local rest).
"""

import argparse
import functools
import os
import sys
import time

if "--aot" in sys.argv:         # the real Mosaic lowering, from the CPU
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["HETU_PALLAS_INTERPRET"] = "0"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

#: Ling-3.0-flash-VL's KDA layers in the video cell: tokens a pack,
#: heads, head size, slots, KDA layers
C, H, D, SLOTS, LAYERS = 2048, 32, 128, 72, 10
#: slots that decode beside the pack
LIVE = 64
#: runs ``(slot, first position, tokens)`` in pack order
PACKS = {
    "one-run": [(3, 2048, 2048)],
    "four-runs": [(5, 0, 512), (9, 1024, 512), (11, 1024, 512),
                  (20, 512, 512)],
}


def operands(key, rows):
    """``rows`` tokens' operands as the mixer makes them (unit q, k; g
    in (-5, 0))."""
    ks = jax.random.split(key, 6)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32)
    q, k, v = (normal(ks[i], (rows, H, D)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * jax.nn.sigmoid(2.0 * normal(ks[3], (rows, H, D)))
    return q, k, v, g, jax.nn.sigmoid(normal(ks[4], (rows, H)))


def draw(key, runs):
    """A pack's operands and where its rows stand."""
    slot, pos = np.zeros(C, np.int32), np.zeros(C, np.int32)
    valid, used = np.zeros(C, bool), 0
    for s, p0, n in runs:
        slot[used:used + n], valid[used:used + n] = s, True
        pos[used:used + n] = np.arange(p0, p0 + n)
        used += n
    return operands(key, C), (jnp.asarray(slot), jnp.asarray(pos),
                              jnp.asarray(valid))


def draw_rows(key):
    """The decode rows' operands (one token a slot) and which slots are
    live — ``LIVE`` of them, spread over the leaf — and fresh (one)."""
    dead = np.linspace(0, SLOTS - 1, SLOTS - LIVE).round().astype(int)
    live = np.ones(SLOTS, bool)
    live[dead] = False
    fresh = np.zeros(SLOTS, bool)
    fresh[np.flatnonzero(live)[1]] = True
    return operands(key, SLOTS), (jnp.asarray(live), jnp.asarray(fresh))


def state_leaf(key):
    return 0.1 * jax.random.normal(key, (LAYERS, SLOTS, H, D, D),
                                   jnp.float32)


def forms(heads):
    from hetu_tpu.ops import kda
    from hetu_tpu.ops.kda_pallas import hetu_kda_scan, kda_head_block
    out = {"jnp": kda.kda_scan}
    for hb in heads or [kda_head_block(H, D, D)]:
        out[f"kernel/{hb}"] = functools.partial(hetu_kda_scan,
                                                head_block=hb)
    return out


def update_forms(heads):
    """The decode rows' update: the ``jax.numpy`` form (a gather, the
    formula, a scatter) beside the kernel at each head block."""
    from hetu_tpu.ops import kda
    from hetu_tpu.ops.kda_pallas import hetu_kda_update, kda_head_block

    def on(fn, **kw):
        return lambda *a, layer: fn(*a[:-1], layer=layer, fresh=a[-1],
                                    **kw)
    out = {"jnp": on(kda.kda_update)}
    for hb in heads or [kda_head_block(H, D, D)]:
        out[f"kernel/{hb}"] = on(hetu_kda_update, head_block=hb)
    return out


def looped(fn, n):
    """``n`` layer calls in one dispatch, the leaf carried (donated),
    each call's ``q`` chained to the last one's ``o``."""
    def run(ops, buf, where):
        def body(i, c):
            buf, o = c
            o, buf = fn(ops[0] + 1e-30 * o, *ops[1:], buf, *where,
                        layer=(i % LAYERS).astype(jnp.int32))
            return buf, o
        return jax.lax.fori_loop(0, n, body,
                                 (buf, jnp.zeros_like(ops[2])))
    return jax.jit(run, donate_argnums=(1,))


def aot_main(args):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
    ops = (sds((C, H, D)),) * 4 + (sds((C, H)),)
    where = (sds((C,), jnp.int32), sds((C,), jnp.int32), sds((C,), bool))
    which = forms
    if args.update:
        ops = (sds((SLOTS, H, D)),) * 4 + (sds((SLOTS, H)),)
        where = (sds((SLOTS,), bool),) * 2
        which = update_forms
    for name, fn in which(args.heads).items():
        t0 = time.perf_counter()
        c = looped(fn, 2).lower(ops, sds((LAYERS, SLOTS, H, D, D)),
                                where).compile()
        text = c.as_text()
        print(f"{name}: compiled for {topo.devices[0].device_kind} in "
              f"{time.perf_counter() - t0:.1f} s, "
              f"{text.count('tpu_custom_call')} kernel call(s), temp "
              f"{c.memory_analysis().temp_size_in_bytes / 2**20:.0f} MiB")


def dots_main(args):
    """The repo's first float32-highest dot inside a kernel, alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    hi = jax.lax.Precision.HIGHEST
    hb, reps, steps = 8, 16, 256

    def one(a_ref, b_ref, o_ref):       # a head at a time
        for j in range(hb):
            acc = a_ref[j]
            for _ in range(reps):
                acc = jnp.dot(acc, b_ref[j], precision=hi,
                              preferred_element_type=jnp.float32)
            o_ref[j] = acc

    def batched(a_ref, b_ref, o_ref):   # over the heads
        acc = a_ref[...]
        for _ in range(reps):
            acc = jnp.einsum("hik,hkv->hiv", acc, b_ref[...],
                             precision=hi,
                             preferred_element_type=jnp.float32)
        o_ref[...] = acc
    a = jax.random.normal(jax.random.key(0), (steps * hb, 64, D)) * 0.1
    b = jax.random.normal(jax.random.key(1), (steps * hb, D, D)) * 0.05
    for name, body in (("a head at a time", one), ("batched", batched)):
        f = jax.jit(pl.pallas_call(
            body, grid=(steps,),
            in_specs=[pl.BlockSpec((hb, 64, D), lambda i: (i, 0, 0)),
                      pl.BlockSpec((hb, D, D), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((hb, 64, D), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",))))
        o = jax.block_until_ready(f(a, b))
        t0 = time.perf_counter()
        o = jax.block_until_ready(f(a, b))
        dt = time.perf_counter() - t0
        want = a
        for _ in range(reps):
            want = jnp.einsum("hik,hkv->hiv", want, b, precision=hi)
        n = steps * hb * reps
        print(f"(64,128)@(128,128) float32 highest, {name}: "
              f"{dt / n * 1e6:.3f} us a dot "
              f"({2 * 64 * D * D * 6 * n / dt / 1e12:.1f} TFLOP/s of "
              f"bf16 passes), max diff from XLA's "
              f"{float(jnp.abs(o - want).max()):.2e}")


def profile_main(args):
    """Each form's device seconds by instruction over ten layer calls
    (the kernel's own time apart from the XLA around it; of the jnp
    form also the piece loop's ``while`` events)."""
    from benchmark.trace import reduce_trace
    from jax.profiler import ProfileData
    import glob
    ops, where = draw(jax.random.key(0), PACKS["one-run"])
    for name, form in forms(args.heads).items():
        fn = looped(form, LAYERS)
        buf = fn(ops, state_leaf(jax.random.key(1)), where)[0]
        out = os.path.join("chiprun_out", "kda_profile",
                           name.replace("/", "_"))
        jax.profiler.start_trace(out)
        jax.block_until_ready(fn(ops, buf, where))
        jax.profiler.stop_trace()
        del buf
        path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        r = reduce_trace(path, top=40 if name == "jnp" else 6)
        print(f"{name}: busy {r['busy_s'] * 1e3:.2f} ms over {LAYERS} "
              f"calls; self time by instruction (ms a call, calls a "
              f"layer call):")
        for op, s in r["device_ops"]:
            print(f"  {op:50s} {s / LAYERS * 1e3:8.4f} "
                  f"{r['op_calls'][op] / LAYERS:8.1f}")
        print(f"  all {sum(r['op_seconds'].values()) / LAYERS * 1e3:.4f}")
        if name != "jnp":
            continue
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                whiles = sorted(
                    (ev.duration_ns for ev in line.events
                     if ev.name.lstrip("%").startswith("while")),
                    reverse=True)
                print(f"  while events (ms): "
                      f"{[round(w * 1e-6, 3) for w in whiles[:LAYERS + 2]]}")


def timed(label, which, ops, where, slots, rows, calls):
    """Each form's ms a call over ``calls`` chained layer calls, and the
    largest difference of its first call from the ``jax.numpy`` form's
    on ``rows`` of ``o`` and on the states of ``slots``."""
    first = {}
    for name, fn in which.items():
        one, many = looped(fn, 1), looped(fn, calls)
        buf, o = one(ops, state_leaf(jax.random.key(1)), where)
        first[name] = (o[rows], buf[0, slots])
        buf = jax.block_until_ready(many(ops, buf, where))[0]
        t0 = time.perf_counter()
        buf = jax.block_until_ready(many(ops, buf, where))[0]
        ms = (time.perf_counter() - t0) / calls * 1e3
        del buf
        diff = "" if name == "jnp" else (
            f"  max |o - jnp's| "
            f"{float(jnp.abs(first[name][0] - first['jnp'][0]).max()):.2e}"
            f", state "
            f"{float(jnp.abs(first[name][1] - first['jnp'][1]).max()):.2e}")
        print(f"{label:10s} {name:10s} {ms:8.3f} ms a call{diff}",
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--dots", action="store_true")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--update", action="store_true")
    ap.add_argument("--heads", type=int, nargs="*")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("packs", nargs="*")
    args = ap.parse_args()
    if args.aot:
        return aot_main(args)
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}")
    if args.dots:
        dots_main(args)
    if args.profile:
        profile_main(args)
    if args.update:
        # one layer call of the decode rows at the cell's shape; the
        # rows of slots that are not live differ by contract
        ops, where = draw_rows(jax.random.key(0))
        live = np.flatnonzero(np.asarray(where[0]))
        return timed(f"{LIVE}/{SLOTS}-live", update_forms(args.heads), ops,
                     where, live, live, args.calls)
    for pack in args.packs or PACKS:
        ops, where = draw(jax.random.key(0), PACKS[pack])
        timed(pack, forms(args.heads), ops, where,
              [r[0] for r in PACKS[pack]], slice(None), args.calls)


if __name__ == "__main__":
    main()
