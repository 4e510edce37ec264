"""Aux-subsystem tests: profiler, straggler monitor, coordinator
(native C++ + python fallback), elastic failure detection + replan.

Parity targets: SURVEY §5.1/5.3/5.8 (``impl/profiler/profiler.h:25``,
``engine/straggler.py:20``, ``heturpc_elastic_server.py:39-559``,
``protos/heturpc.proto:10-70``)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.engine.elastic import ElasticController, HeartbeatSender
from hetu_tpu.engine.straggler import StragglerMonitor, replan_for_stragglers
from hetu_tpu.models import GPTConfig
from hetu_tpu.rpc import Coordinator, CoordinatorClient
from hetu_tpu.tools.galvatron import ModelDims, TPUTopology
from hetu_tpu.utils.profiler import (
    StepProfiler, device_memory_stats, live_array_bytes,
)


def test_step_profiler_separates_compile():
    prof = StepProfiler()

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((128, 128))
    for _ in range(4):
        with prof.step():
            f(x).block_until_ready()
    st = prof.stats()
    assert st.count == 3 and st.compile_s is not None
    assert st.compile_s >= st.mean_s  # first call included tracing
    assert st.tokens_per_sec(1000) > 0


def test_memory_helpers():
    stats = device_memory_stats()
    assert isinstance(stats, dict)  # may be empty on CPU backend
    assert live_array_bytes() >= 0


def test_straggler_monitor_and_replan():
    mon = StragglerMonitor(size=256, iters=2)
    report = mon.measure(jax.devices()[:4])
    assert len(report.ratios) == 4
    assert min(report.ratios.values()) == 1.0
    # Synthetic straggler: real timings of virtual CPU devices (one physical
    # host) are noise, so pin them before asserting — pretend device 3 is
    # 3x slower and everyone else healthy.
    report.ratios.update({i: 1.0 for i in report.ratios})
    report.ratios[3] = 3.0
    assert report.stragglers(1.5) == [3]
    dims = ModelDims.from_config(GPTConfig.tiny(), seq_len=128,
                                 global_batch=8)
    topo = TPUTopology(num_devices=4)
    healthy, cand = replan_for_stragglers(report, dims, topo)
    assert 3 not in healthy and len(healthy) == 2
    assert cand is not None
    cand.strategy.validate(len(healthy))


@pytest.mark.parametrize("native", [True, False], ids=["cpp", "python"])
def test_coordinator_rank_kv_barrier_heartbeat(native):
    with Coordinator(prefer_native=native) as coord:
        if native:
            assert coord.native, "native coordinator failed to build/start"
        c1 = CoordinatorClient(coord.port)
        c2 = CoordinatorClient(coord.port)
        assert c1.ping()
        # idempotent rank assignment
        assert c1.rank("worker-a") == 0
        assert c2.rank("worker-b") == 1
        assert c1.rank("worker-a") == 0
        # typed KV (json values survive)
        c1.put("strategy", {"dp": 4, "tp": 2})
        assert c2.get("strategy") == {"dp": 4, "tp": 2}
        assert c2.get("missing", 42) == 42
        # barrier across two clients
        results = []

        def waiter():
            c = CoordinatorClient(coord.port)
            c.barrier("sync1", 2, "worker-b")
            results.append("b")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.2)
        assert not results  # still blocked
        c1.barrier("sync1", 2, "worker-a")
        t.join(timeout=10)
        assert results == ["b"]
        # heartbeats + status
        c1.heartbeat("worker-a")
        c2.heartbeat("worker-b")
        alive, dead = c1.status(5000)
        assert set(alive) == {"worker-a", "worker-b"} and not dead


def test_elastic_failure_detection_and_replan():
    with Coordinator(prefer_native=True) as coord:
        hb_a = HeartbeatSender(coord.port, "w0", interval_s=0.1).start()
        hb_b = HeartbeatSender(coord.port, "w1", interval_s=0.1).start()
        ctrl = ElasticController(coord.port, timeout_ms=500)
        time.sleep(0.3)
        alive, dead = ctrl.check()
        assert set(alive) == {"w0", "w1"} and not dead
        # kill one worker → detected dead after timeout
        hb_b.stop()
        time.sleep(1.0)
        alive, dead = ctrl.check()
        assert "w1" in dead and "w0" in alive
        # replan for survivors (8 → 6 alive → largest pow2 = 4)
        dims = ModelDims.from_config(GPTConfig.tiny(), seq_len=128,
                                     global_batch=8)
        topo = TPUTopology(num_devices=8)
        s = ctrl.recovery_plan(dims, topo, n_alive_devices=6)
        assert s is not None and s.num_devices == 4
        hb_a.stop()


def test_elastic_recovery_plan_hetero_uses_all_survivors():
    """Ampelos parity (strategy_ampelos.py:906): a non-pow2 survivor
    count with known depth plans a hetero pipeline over ALL survivors
    instead of stranding devices on the largest pow2 subset."""
    from hetu_tpu.parallel.hetero import HeteroStrategy
    from hetu_tpu.parallel.strategy import Strategy

    ctrl = ElasticController  # recovery_plan is static: no coordinator
    dims = ModelDims.from_config(GPTConfig.tiny(), seq_len=128,
                                 global_batch=8)
    topo = TPUTopology(num_devices=8)

    # 7 alive, 8 layers: hetero over 4+2+1 (all 7 devices busy) beats
    # a stranded-uniform plan on 4
    s = ctrl.recovery_plan(dims, topo, n_alive_devices=7, num_layers=8)
    assert isinstance(s, HeteroStrategy)
    assert sum(st.n_devices for st in s.stages) == 7
    assert sum(st.layers for st in s.stages) == 8
    # no real ids known → device_ids must stay unbound (fabricated
    # 0..6 would target a dead device whenever a low id died)
    assert s.device_ids is None

    # real survivor ids (device 2 died): the plan binds exactly those
    alive = [0, 1, 3, 4, 5, 6, 7]
    s_ids = ctrl.recovery_plan(dims, topo, n_alive_devices=7,
                               num_layers=8, alive_device_ids=alive)
    assert isinstance(s_ids, HeteroStrategy)
    assert sorted(s_ids.device_ids) == alive
    # widest stage carries the most layers (layers ∝ throughput)
    widths = [st.tp for st in s.stages]
    layers = [st.layers for st in s.stages]
    assert layers[widths.index(max(widths))] == max(layers)

    # pow2 survivor count: uniform strategy as before, even with depth
    s8 = ctrl.recovery_plan(dims, topo, n_alive_devices=8, num_layers=8)
    assert isinstance(s8, Strategy)

    # unknown depth: pow2 fallback (old behavior)
    s7 = ctrl.recovery_plan(dims, topo, n_alive_devices=7)
    assert isinstance(s7, Strategy) and s7.num_devices == 4

    # hetero opt-out honored
    s_no = ctrl.recovery_plan(dims, topo, n_alive_devices=7,
                              num_layers=8, allow_hetero=False)
    assert isinstance(s_no, Strategy) and s_no.num_devices == 4

    # too-shallow model (1 layer < 2 stages): falls back to uniform
    s1 = ctrl.recovery_plan(dims, topo, n_alive_devices=7, num_layers=1)
    assert isinstance(s1, Strategy)


def test_yaml_experiment_configs():
    """YAML configs (SURVEY §5.6 parity) compile to framework objects;
    every shipped example config builds and validates."""
    import glob
    import os
    from hetu_tpu.parallel.hetero import HeteroStrategy
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.utils.config import build_experiment
    cfgs = sorted(glob.glob(os.path.join(
        os.path.dirname(__file__), "..", "examples", "configs", "*.yaml")))
    assert len(cfgs) >= 3
    seen_hetero = False
    for path in cfgs:
        exp = build_experiment(path)
        st = exp["strategy"]
        assert isinstance(st, (Strategy, HeteroStrategy))
        st.validate(8)
        assert exp["model"] is not None
        if isinstance(st, HeteroStrategy):
            seen_hetero = True
            assert exp["model_config"].num_layers == st.num_layers
    assert seen_hetero


def test_metrics_logger_plot(tmp_path):
    """Loss plotting parity (reference engine/trainer.py:779)."""
    from hetu_tpu.utils.logging import MetricsLogger

    m = MetricsLogger(echo=False)
    for i in range(5):
        m.log(i * 10, loss=5.0 - i, grad_norm=1.0)
    out = m.plot(str(tmp_path / "loss.png"), keys=("loss", "grad_norm"))
    import os
    assert os.path.getsize(out) > 1000


def test_elastic_resume_prefers_live_state(monkeypatch, tmp_path):
    """Survivor-path recovery reshards LIVE state in memory — NO
    checkpoint read (VERDICT r3 item 6; reference restarts from disk,
    ``heturpc_elastic_server.py:497-559``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from hetu_tpu import optim
    from hetu_tpu.engine import make_plan, init_state, build_train_step
    from hetu_tpu.engine.elastic import elastic_resume
    from hetu_tpu.models import GPTLMHeadModel
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.utils import dist_checkpoint

    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-3)
    plan8 = make_plan(model, opt, Strategy(dp=2, tp=4))
    state = init_state(model, opt, plan8, jax.random.key(0),
                       dtype=jnp.float32)
    step8 = build_train_step(model, opt, plan8)
    ids = jax.random.randint(jax.random.key(1), (8, 17), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    for _ in range(2):
        state, m = step8(state, plan8.shard_batch(batch))

    # persist a checkpoint the live path must NOT touch
    ckpt = str(tmp_path / "ck")
    dist_checkpoint.save_checkpoint_distributed(ckpt, state)
    oracle_plan, oracle_state = elastic_resume(
        model, opt, Strategy(dp=2, tp=2), devices=jax.devices()[:4],
        state=None, checkpoint_dir=ckpt)

    def _no_disk(*a, **kw):
        raise AssertionError("live-state resume read the checkpoint")
    monkeypatch.setattr(dist_checkpoint, "load_checkpoint_distributed",
                        _no_disk)

    # "lose" devices 4..7: recovery plan on the surviving half
    new_plan, new_state = elastic_resume(
        model, opt, Strategy(dp=2, tp=2), devices=jax.devices()[:4],
        state=state, checkpoint_dir=ckpt)
    assert {d.id for leaf in jax.tree.leaves(new_state.params)
            for d in leaf.sharding.device_set} == {0, 1, 2, 3}

    # continuation must be numerically identical to the disk path
    step4 = build_train_step(model, opt, new_plan)
    _, m_live = step4(new_state, new_plan.shard_batch(batch))
    _, m_disk = step4(oracle_state, new_plan.shard_batch(batch))
    np.testing.assert_allclose(float(m_live["loss"]),
                               float(m_disk["loss"]), rtol=1e-6)


@pytest.mark.slow
def test_elastic_resume_disk_fallback_when_reshard_raises(monkeypatch,
                                                         tmp_path):
    """The live reshard can be impossible (e.g. the only copy of a shard
    lived on the dead devices): elastic_resume must warn-then-load from
    the sharded checkpoint — and with NO checkpoint_dir it must re-raise
    instead of limping on (``elastic.py`` fallback paths)."""
    import jax.numpy as jnp
    from hetu_tpu import optim
    from hetu_tpu.engine import init_state, make_plan
    from hetu_tpu.engine.elastic import elastic_resume
    from hetu_tpu.models import GPTLMHeadModel
    from hetu_tpu.parallel import switch as switch_mod
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.utils import dist_checkpoint

    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-3)
    plan8 = make_plan(model, opt, Strategy(dp=2, tp=4))
    state = init_state(model, opt, plan8, jax.random.key(0),
                       dtype=jnp.float32)
    ckpt = str(tmp_path / "ck")
    dist_checkpoint.save_checkpoint_distributed(ckpt, state)

    def reshard_impossible(s, p):
        raise RuntimeError("shards lost with the dead devices")

    monkeypatch.setattr(switch_mod, "switch_strategy",
                        reshard_impossible)
    # live state present but unreshardable + a checkpoint: disk fallback
    new_plan, new_state = elastic_resume(
        model, opt, Strategy(dp=2, tp=2), devices=jax.devices()[:4],
        state=state, checkpoint_dir=ckpt)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(new_state.params)):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b)))
    assert {d.id for leaf in jax.tree.leaves(new_state.params)
            for d in leaf.sharding.device_set} <= {0, 1, 2, 3}
    # no checkpoint to fall back to: the reshard error must surface
    with pytest.raises(RuntimeError, match="shards lost"):
        elastic_resume(model, opt, Strategy(dp=2, tp=2),
                       devices=jax.devices()[:4], state=state,
                       checkpoint_dir=None)
    # dead controller (no live state) and no checkpoint_dir: explicit
    with pytest.raises(ValueError, match="nothing to resume"):
        elastic_resume(model, opt, Strategy(dp=2, tp=2),
                       devices=jax.devices()[:4], state=None,
                       checkpoint_dir=None)


def test_recovery_plan_hetero_adoption_boundary():
    """Hetero-vs-stranded-uniform adoption at a non-pow2 survivor count
    with REAL alive ids: adopted only when the bubble-discounted
    throughput of using ALL survivors beats the stranded-pow2 subset —
    few microbatches (deep bubble) must fall back to uniform."""
    from hetu_tpu.parallel.hetero import HeteroStrategy
    from hetu_tpu.parallel.strategy import Strategy

    dims = ModelDims.from_config(GPTConfig.tiny(), seq_len=128,
                                 global_batch=8)
    topo = TPUTopology(num_devices=8)
    alive = [0, 1, 2, 4, 5, 6]        # device 3 and 7 died: 6 alive
    # 8 microbatches: hetero over 4+2 (pp=2) → eff 6*8/9 = 5.33 > 4
    s = ElasticController.recovery_plan(
        dims, topo, n_alive_devices=6, num_layers=8,
        num_microbatches=8, alive_device_ids=alive)
    assert isinstance(s, HeteroStrategy)
    assert sum(st.n_devices for st in s.stages) == 6
    assert sorted(s.device_ids) == alive       # binds REAL survivors
    # 1 microbatch: the pipeline bubble eats the gain (6*1/2 = 3 < 4):
    # stranded-uniform on the pow2 subset wins
    s1 = ElasticController.recovery_plan(
        dims, topo, n_alive_devices=6, num_layers=8,
        num_microbatches=1, alive_device_ids=alive)
    assert isinstance(s1, Strategy) and s1.num_devices == 4
    # candidate_filter governs BOTH kinds: it must veto the hetero plan
    # (pp=2 pipeline) AND constrain the uniform fallback
    s2 = ElasticController.recovery_plan(
        dims, topo, n_alive_devices=6, num_layers=8,
        num_microbatches=8, alive_device_ids=alive,
        candidate_filter=lambda st: getattr(st, "tp", 1) == 1
        and st.pp == 1)
    assert isinstance(s2, Strategy)
    assert s2.tp == 1 and s2.pp == 1


@pytest.mark.parametrize("native", [True, False], ids=["cpp", "python"])
def test_coordinator_two_generation_race(native):
    """Partial-partition hardening (VERDICT r4 weak #7): a generation-0
    straggler that stopped heartbeating but kept its socket must not
    perturb generation 1 — rank assignment stays fresh and stable for
    the new names, the generations' KV namespaces stay independent under
    interleaved writes (including a late straggler write racing the new
    generation), gen-1's barrier completes with only gen-1 members while
    the straggler blocks on a DIFFERENT barrier name, and STATUS reports
    exactly the non-beating worker dead."""
    with Coordinator(prefer_native=native) as coord:
        g0 = [CoordinatorClient(coord.port) for _ in range(3)]
        for r, c in enumerate(g0):
            assert c.rank(f"g0-w{r}") == r
            c.heartbeat(f"g0-w{r}")
        g0[0].put("ckpt-g0", {"step": 5})

        # g0-w2 partitions: no more heartbeats, socket stays open
        time.sleep(0.8)
        for r in (0, 1):
            g0[r].heartbeat(f"g0-w{r}")
        alive, dead = g0[0].status(500)
        assert "g0-w2" in dead and "g0-w0" in alive and "g0-w1" in alive

        # the straggler parks on ITS generation's barrier name
        parked = []

        def straggle():
            try:
                g0[2].barrier("resume-g0", 3, "g0-w2")
                parked.append("released")      # must never happen
            except Exception:
                parked.append("errored")
        t0 = threading.Thread(target=straggle, daemon=True)
        t0.start()

        # generation 1 registers WHILE the straggler is parked and
        # meanwhile keeps writing stale gen-0 keys
        g1 = [CoordinatorClient(coord.port) for _ in range(2)]
        ranks = [c.rank(f"g1-w{r}") for r, c in enumerate(g1)]
        # FRESH: gen-0 holds 0..2 (straggler's rank 2 included — it may
        # still be alive somewhere), so recycling would collide ranks
        # across generations
        assert ranks == [3, 4], ranks
        assert [c.rank(f"g1-w{r}") for r, c in enumerate(g1)] == ranks
        g0[0].put("ckpt-g0", {"step": 6})      # late gen-0 write
        g1[0].put("ckpt-g1", {"step": 6, "resharded": True})
        g0[1].put("ckpt-g0", {"step": 7})      # straggler-side write
        # namespaces stayed independent in both directions
        assert g1[1].get("ckpt-g1") == {"step": 6, "resharded": True}
        assert g1[1].get("ckpt-g0") == {"step": 7}
        assert g0[0].get("ckpt-g1") == {"step": 6, "resharded": True}

        # gen-1's barrier completes with only gen-1 members
        done = []

        def b1():
            c = CoordinatorClient(coord.port)
            c.barrier("resume-g1", 2, "g1-w1")
            done.append("ok")
        t1 = threading.Thread(target=b1)
        t1.start()
        time.sleep(0.2)
        assert not done
        g1[0].barrier("resume-g1", 2, "g1-w0")
        t1.join(timeout=10)
        assert done == ["ok"]
        assert not parked                      # straggler still parked


@pytest.mark.parametrize("native", [True, False], ids=["cpp", "python"])
def test_coordinator_auth_token(native, monkeypatch):
    """Shared-secret auth (VERDICT r4 weak #7 'no auth'): a token-bearing
    coordinator rejects wrong tokens and unauthenticated commands
    (connection closed), keeps PING open for liveness probes, accepts
    the right token (explicit or via HETU_COORD_TOKEN, the launcher's
    ship-to-workers path), and a token-less server stays back-compatible
    with AUTH-sending clients."""
    import os
    import socket

    with Coordinator(prefer_native=native, token="s3cret") as coord:
        # right token: full protocol works
        c = CoordinatorClient(coord.port, token="s3cret")
        assert c.rank("w0") == 0
        c.put("k", {"v": 1})
        assert c.get("k") == {"v": 1}
        # wrong token: refused at connect
        with pytest.raises(ConnectionError):
            CoordinatorClient(coord.port, token="wrong")
        # unauthenticated command: server answers ERR and closes
        raw = socket.create_connection(("127.0.0.1", coord.port),
                                       timeout=5)
        raw.sendall(b"RANK intruder\n")
        assert b"ERR auth required" in raw.recv(4096)
        assert raw.recv(4096) == b""         # closed
        raw.close()
        # the intruder name must NOT have taken a rank
        assert c.rank("w1") == 1
        # PING stays open for liveness probes (explicit empty token so
        # the client sends no AUTH)
        p = CoordinatorClient(coord.port, token="")
        assert p.ping()
        # env-var path (how workers inherit the pool token)
        monkeypatch.setenv("HETU_COORD_TOKEN", "s3cret")
        assert CoordinatorClient(coord.port).rank("w0") == 0
        monkeypatch.delenv("HETU_COORD_TOKEN")

    with Coordinator(prefer_native=native) as coord:
        # token-less server: AUTH is an idempotent OK (clients can be
        # config-agnostic)
        c = CoordinatorClient(coord.port, token="anything")
        assert c.rank("a") == 0
