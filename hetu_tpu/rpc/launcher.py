"""Multi-process launch + elastic restart plane.

TPU-native counterpart of the reference's launcher stack:
``rpc/pssh_start.py:17`` (SSH fan-out, per-process env + log files) and
``rpc/heturpc_elastic_server.py:497-559`` (death detection → restart the
worker pool, resume from checkpoint). Here the fan-out is local
``subprocess`` workers (the SSH hop is an env-provided command prefix away)
and the cross-process device runtime is ``jax.distributed`` — the
Coordinator supplies rank assignment, the KV used to exchange the JAX
coordinator address, heartbeats, and barriers; JAX's own distributed
service then owns collective bootstrap (the role NCCL-id exchange plays in
the reference).

Elastic model (same as the reference's): individual processes cannot be
re-admitted into a running JAX job, so on any worker death the pool kills
the generation and relaunches all workers; workers resume from the latest
(sharded) checkpoint. Generations are namespaced in worker names and KV
keys. Single-controller flows do better: when the controller process
survives the failure, ``engine.elastic.elastic_resume`` reshards its LIVE
train state onto the recovery plan in memory (cross_topology_switch) and
no checkpoint is read — disk is only the dead-controller fallback.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Optional, Sequence

from hetu_tpu.rpc.client import CoordinatorClient
from hetu_tpu.rpc.coordinator import Coordinator
from hetu_tpu.utils.logging import get_logger


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclasses.dataclass
class DistContext:
    """A worker's view of the cluster after bootstrap."""

    rank: int
    num_processes: int
    generation: int
    client: CoordinatorClient
    heartbeat: Optional[object]   # HeartbeatSender (imported lazily —
                                  # engine.elastic imports rpc.client)

    def shutdown(self):
        if self.heartbeat is not None:
            self.heartbeat.stop()
        self.client.close()


def bootstrap_distributed(*, coord_port: Optional[int] = None,
                          num_processes: Optional[int] = None,
                          rank: Optional[int] = None,
                          name: Optional[str] = None,
                          heartbeat: bool = True,
                          timeout_s: float = 60.0) -> DistContext:
    """Connect to the Coordinator, resolve rank, and bring up
    ``jax.distributed`` across the worker set.

    Reference flow: ``distributed_init`` → Connect/GetRank → NCCL-id via
    coordinator (SURVEY §3.1). Here: rank from the Coordinator (or the
    launcher's HETU_RANK), JAX service address via the coordinator KV
    (rank 0 publishes, everyone else polls), then
    ``jax.distributed.initialize``.
    """
    port = coord_port if coord_port is not None \
        else int(os.environ["HETU_COORD_PORT"])
    coord_host = os.environ.get("HETU_COORD_HOST", "127.0.0.1")
    n = num_processes if num_processes is not None \
        else int(os.environ.get("HETU_NUM_PROCS", "1"))
    gen = int(os.environ.get("HETU_GENERATION", "0"))
    name = name or os.environ.get("HETU_WORKER_NAME",
                                  f"worker-{os.getpid()}")
    client = CoordinatorClient(port, host=coord_host)
    if rank is None:
        env_rank = os.environ.get("HETU_RANK")
        rank = int(env_rank) if env_rank is not None else client.rank(name)

    if n > 1:
        key = f"jax_coordinator/g{gen}"
        if rank == 0:
            # cross-host workers must publish a routable address, not
            # loopback; HETU_ADVERTISE_HOST overrides, else hostname when
            # the coordinator itself is non-local
            if coord_host in ("127.0.0.1", "localhost"):
                my_host = "127.0.0.1"
            else:
                import socket as _socket
                my_host = _socket.gethostname()
            my_host = os.environ.get("HETU_ADVERTISE_HOST", my_host)
            addr = f"{my_host}:{_free_port()}"
            client.put(key, addr)
        else:
            deadline = time.monotonic() + timeout_s
            addr = client.get(key)
            while addr is None:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"rank {rank}: no {key} published within "
                        f"{timeout_s}s")
                time.sleep(0.05)
                addr = client.get(key)
        import jax
        from hetu_tpu.telemetry.flight import flight_record
        # collective bootstraps are the classic distributed-hang site:
        # bracket the blocking initialize in the black box so a wedged
        # rendezvous is attributable post-mortem
        flight_record("collective_bootstrap", phase="start", rank=rank,
                      num_processes=n, addr=addr)
        jax.distributed.initialize(addr, num_processes=n, process_id=rank)
        flight_record("collective_bootstrap", phase="done", rank=rank,
                      num_processes=n)

    if heartbeat:
        from hetu_tpu.engine.elastic import HeartbeatSender
        hb = HeartbeatSender(port, name).start()
    else:
        hb = None
    return DistContext(rank, n, gen, client, hb)


class ElasticWorkerPool:
    """Spawn N worker processes; on any death, restart the generation.

    Parity: the elastic server's restart-with-PSSH-pool loop
    (``heturpc_elastic_server.py:497-559``) with ``max_restart_times``
    semantics from the host yaml (``pssh_start.py:27-36``).
    """

    #: default worker platform: the CPU-simulation flow (one virtual
    #: device per process). Pass ``platform_env={}`` (or your own) to run
    #: workers on real TPU hosts with the inherited environment.
    CPU_SIM_ENV = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "JAX_PLATFORMS": "cpu",
    }

    def __init__(self, script: str, num_workers: int, *,
                 args: Sequence[str] = (),
                 max_restarts: int = 1,
                 log_dir: Optional[str] = None,
                 env: Optional[dict] = None,
                 platform_env: Optional[dict] = None,
                 ssh_hosts: Optional[Sequence[str]] = None,
                 ssh_cmd: Sequence[str] = ("ssh", "-tt"),
                 coordinator_host: Optional[str] = None,
                 poll_s: float = 0.2):
        self.script = script
        self.num_workers = num_workers
        self.args = list(args)
        # multi-host fan-out à la pssh_start.py: worker i runs on
        # ssh_hosts[i % len] with its env serialized into the remote
        # command (the coordinator address must then be reachable —
        # bind-all is the operator's call, as in the reference)
        self.ssh_hosts = list(ssh_hosts) if ssh_hosts else None
        # transport argv prefix: the hop command that receives
        # ``host remote-shell-string...`` — ("ssh", "-tt") in production
        # (reference: parallel-ssh, ``pssh_start.py:17``); tests and
        # exotic fabrics substitute a shim with the same contract (the
        # remote words are shell-quoted, so the hop must run them
        # through a shell like sshd does)
        self.ssh_cmd = list(ssh_cmd)
        # routable address of THIS machine for remote workers' coordinator
        # connections (required with ssh_hosts)
        self.coordinator_host = coordinator_host
        if self.ssh_hosts and not coordinator_host:
            raise ValueError(
                "ssh_hosts needs coordinator_host (a routable address of "
                "the launcher machine — remote workers must reach the "
                "coordinator and it binds 127.0.0.1 otherwise)")
        self.max_restarts = max_restarts
        self.log_dir = log_dir
        self.extra_env = dict(env or {})
        self.platform_env = dict(self.CPU_SIM_ENV if platform_env is None
                                 else platform_env)
        self.poll_s = poll_s
        self.coordinator: Optional[Coordinator] = None
        self.procs: list[subprocess.Popen] = []
        self.generation = 0
        self._logs: list = []

    # -- lifecycle -----------------------------------------------------------
    def __enter__(self):
        # multi-host fleets need a reachable coordinator; every pool
        # gets a fresh bearer token (shipped to workers via
        # HETU_COORD_TOKEN) — mandatory when binding beyond loopback
        import secrets
        self._token = secrets.token_hex(16)
        self.coordinator = Coordinator(
            bind="0.0.0.0" if self.ssh_hosts else "127.0.0.1",
            token=self._token)
        return self

    def __exit__(self, *exc):
        self._kill_all()
        if self.coordinator is not None:
            self.coordinator.shutdown()
        return False

    def _worker_env(self, rank: int) -> dict:
        env = dict(os.environ)
        env.update(self.platform_env)
        env.update(self.extra_env)
        # launcher-owned keys always win — they define the worker identity
        env.update({
            "HETU_COORD_PORT": str(self.coordinator.port),
            "HETU_COORD_TOKEN": self._token,
            "HETU_NUM_PROCS": str(self.num_workers),
            "HETU_RANK": str(rank),
            "HETU_GENERATION": str(self.generation),
            "HETU_WORKER_NAME": f"g{self.generation}-w{rank}",
        })
        return env

    def _spawn_all(self):
        self.procs = []
        for r in range(self.num_workers):
            if self.log_dir:
                os.makedirs(self.log_dir, exist_ok=True)
                path = os.path.join(self.log_dir,
                                    f"g{self.generation}-w{r}.log")
                # 0600: worker logs can carry secrets (e.g. a pty-echoed
                # auth token line on ssh fleets)
                log = os.fdopen(os.open(
                    path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600),
                    "w")
            else:
                log = subprocess.DEVNULL
            self._logs.append(log)
            env = self._worker_env(r)
            cmd = [sys.executable, self.script, *self.args]
            stdin = None
            if self.ssh_hosts:
                import shlex
                host = self.ssh_hosts[r % len(self.ssh_hosts)]
                env["HETU_COORD_HOST"] = self.coordinator_host
                hetu_env = [shlex.quote(f"{k}={v}")
                            for k, v in env.items()
                            if k.startswith(("HETU_", "JAX_", "XLA_",
                                             "PYTHONPATH"))
                            and k != "HETU_COORD_TOKEN"]
                # -tt (in the default ssh_cmd): killing the local ssh
                # client drops the remote tty, so the remote worker gets
                # SIGHUP on generation teardown. The auth token travels
                # over the ssh STDIN pipe, never on the remote command
                # line — /proc/<pid>/cmdline is world-readable on every
                # worker host. The remote bootstrap is wrapped in an
                # explicit `sh -c` so csh/fish login shells work, and
                # turns pty echo off (best-effort) before reading the
                # token; the launcher-local log file is 0600 regardless,
                # so even a raced echo never lands world-readable.
                payload = (
                    "stty -echo 2>/dev/null; read -r HETU_COORD_TOKEN; "
                    "export HETU_COORD_TOKEN; exec env "
                    + " ".join(hetu_env) + " python3 "
                    + shlex.quote(self.script) + " "
                    + " ".join(map(shlex.quote, self.args))).rstrip()
                cmd = [*self.ssh_cmd, host, "sh", "-c",
                       shlex.quote(payload)]
                stdin = subprocess.PIPE
            p = subprocess.Popen(cmd, env=env, stdout=log, stderr=log,
                                 stdin=stdin)
            if stdin is not None:
                try:
                    p.stdin.write((self._token + "\n").encode())
                    p.stdin.flush()
                except OSError:
                    # ssh died instantly (unreachable host): leave the
                    # dead proc for the generation-restart loop, exactly
                    # like any other worker death
                    pass
            self.procs.append(p)
        get_logger().info(
            f"pool: generation {self.generation} spawned "
            f"{self.num_workers} workers")

    def _kill_all(self):
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + 5
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
        for log in self._logs:
            if log is not subprocess.DEVNULL and not log.closed:
                log.close()
        self._logs = []

    def kill_worker(self, rank: int, sig=signal.SIGKILL):
        """Fault injection for chaos tests."""
        self.procs[rank].send_signal(sig)

    # -- supervision ---------------------------------------------------------
    def run(self, timeout_s: float = 300.0) -> dict:
        """Launch and supervise until the generation exits cleanly (all rc
        0) or restarts are exhausted. Returns a summary dict."""
        if self.coordinator is None:
            raise RuntimeError("use ElasticWorkerPool as a context manager")
        self._spawn_all()
        deadline = time.monotonic() + timeout_s
        restarts = 0
        while True:
            if time.monotonic() > deadline:
                self._kill_all()
                raise TimeoutError("worker pool timed out")
            codes = [p.poll() for p in self.procs]
            if all(c == 0 for c in codes):
                return {"generations": self.generation + 1,
                        "restarts": restarts, "exit_codes": codes}
            if any(c is not None and c != 0 for c in codes):
                dead = [i for i, c in enumerate(codes)
                        if c is not None and c != 0]
                get_logger().warning(
                    f"pool: generation {self.generation} lost workers "
                    f"{dead} (codes {[codes[i] for i in dead]})")
                self._kill_all()
                if restarts >= self.max_restarts:
                    return {"generations": self.generation + 1,
                            "restarts": restarts, "exit_codes": codes,
                            "failed": True}
                restarts += 1
                self.generation += 1
                self._spawn_all()
            time.sleep(self.poll_s)


@dataclasses.dataclass
class FleetHandle:
    """A launched serving fleet: the router, its replica names, and the
    optional coordinator front door. ``stop()`` tears down front door →
    router → every replica loop (reverse launch order). A REMOTE fleet
    also carries its engine processes (``procs``) — ``stop()`` SIGTERMs
    them after the router lets go, and :meth:`kill_replica_process` is
    the chaos hook (real SIGKILL; the router's heartbeat staleness
    detects it)."""

    router: object                   # serving.router.Router
    replicas: list
    coordinator: Optional[object] = None   # PyCoordinatorServer | None
    port: Optional[int] = None
    procs: dict = dataclasses.field(default_factory=dict)
    #                                ^ name → subprocess.Popen (remote)
    engine_ports: dict = dataclasses.field(default_factory=dict)
    _logs: list = dataclasses.field(default_factory=list)

    def kill_replica_process(self, name: str, sig=signal.SIGKILL):
        """Chaos hook: SIGKILL one remote engine process. Death is
        detected by the router through heartbeat staleness — nothing
        here tells it."""
        self.procs[name].send_signal(sig)

    def stop(self):
        if self.coordinator is not None:
            self.coordinator.stop()
        self.router.stop()
        for p in self.procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + 10
        for p in self.procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
        for log in self._logs:
            if log is not subprocess.DEVNULL and not log.closed:
                log.close()
        self._logs = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def launch_serving_fleet(build_engine=None, n_replicas: int = 2, *,
                         names: Optional[Sequence[str]] = None,
                         roles: Optional[dict] = None,
                         port: Optional[int] = None,
                         bind: str = "127.0.0.1", token: str = "",
                         remote: bool = False,
                         engine_spec: Optional[str] = None,
                         env: Optional[dict] = None,
                         platform_env: Optional[dict] = None,
                         log_dir: Optional[str] = None,
                         spawn_timeout_s: float = 120.0,
                         beat_timeout_s: Optional[float] = None,
                         proxy_kw: Optional[dict] = None,
                         **router_kw) -> FleetHandle:
    """Bring up a serving fleet: N replicas, one load-aware Router over
    them, and — when ``port`` is given — a coordinator speaking the
    full verb set (SUBMIT/RESULT/GENERATE routed fleet-wide,
    FLEET/DRAIN/RESUME, HEALTHZ/METRICS) as the fleet's front door.

    **In-process** (default): each replica is ``build_engine(i)`` — a
    fresh ServingEngine whose background loop registration starts.
    Threads share one process's devices: the single-host shape used by
    ``workloads/rollout_loop.py`` and the router tests.

    **Multi-process** (``remote=True`` — ISSUE 15): one engine PROCESS
    per replica. ``engine_spec`` names a ``module:function`` the child
    resolves and calls with its replica index (closures cannot cross
    the process boundary); each child serves its engine on a private
    line-protocol port (``serving.fleet.replica_main``), the launcher
    waits for it to answer PING, and registers a
    ``RemoteEngineProxy``-backed handle — death detection is heartbeat
    staleness, KV spills and weight pushes travel the wire
    (``docs/SERVING.md`` "Disaggregated fleet"). ``platform_env``
    defaults to the CPU-simulation flow
    (``ElasticWorkerPool.CPU_SIM_ENV``); pass ``{}`` to inherit (real
    TPU hosts). ``roles`` maps replica name → ``prefill|decode|both``
    for P/D disaggregation (both modes).

    ``proxy_kw`` forwards extra keyword arguments to every
    ``RemoteEngineProxy`` (e.g. ``{"use_stream": False}`` to force the
    legacy RESULT-polling transport — the bench's polling baseline).

    Lazy imports keep the launcher importable without jax.
    """
    from hetu_tpu.serving.router import Router

    names = list(names) if names is not None \
        else [f"r{i}" for i in range(n_replicas)]
    if len(names) != n_replicas:
        raise ValueError(f"{len(names)} names for {n_replicas} replicas")
    roles = dict(roles or {})
    if beat_timeout_s is not None:
        router_kw["beat_timeout_s"] = beat_timeout_s
    router = Router(**router_kw)
    handle = FleetHandle(router=router, replicas=names)

    if remote:
        if engine_spec is None:
            raise ValueError(
                "remote=True needs engine_spec='module:function' — a "
                "builder the engine process can import (closures "
                "cannot cross the process boundary)")
        penv = dict(ElasticWorkerPool.CPU_SIM_ENV
                    if platform_env is None else platform_env)
        for i, name in enumerate(names):
            eport = _free_port()
            env_i = dict(os.environ)
            env_i.update(penv)
            env_i.update(env or {})
            env_i.update({
                "HETU_ENGINE_SPEC": engine_spec,
                "HETU_REPLICA_INDEX": str(i),
                "HETU_REPLICA_NAME": name,
                # observability identity: flight-recorder dumps and
                # DUMPOBS bundles are stamped with the replica's P/D
                # role so obs_report/fleet_trace can group them
                "HETU_REPLICA_ROLE": str(roles.get(name, "both")),
                "HETU_ENGINE_PORT": str(eport),
                # the engine ports must enforce the same token as the
                # front door — an unauthenticated replica port would
                # accept STOPENGINE/SWAPWEIGHTS from anyone local
                "HETU_ENGINE_TOKEN": token,
            })
            if log_dir:
                os.makedirs(log_dir, exist_ok=True)
                log = open(os.path.join(log_dir, f"{name}.log"), "w")
                handle._logs.append(log)
            else:
                log = subprocess.DEVNULL
            p = subprocess.Popen(
                [sys.executable, "-m", "hetu_tpu.serving.fleet"],
                env=env_i, stdout=log, stderr=log)
            handle.procs[name] = p
            handle.engine_ports[name] = eport
        # wait for every engine to answer, then register its proxy —
        # registration starts the status poller (= the heartbeat). A
        # replica that fails to come up must not leak its siblings:
        # tear the whole half-launched fleet down before re-raising.
        from hetu_tpu.rpc.client import CoordinatorClient
        from hetu_tpu.serving.fleet import RemoteEngineProxy
        deadline = time.monotonic() + spawn_timeout_s
        try:
            for name in names:
                eport = handle.engine_ports[name]
                while True:
                    proc = handle.procs[name]
                    if proc.poll() is not None:
                        raise RuntimeError(
                            f"fleet replica {name} exited "
                            f"rc={proc.poll()} before serving "
                            f"(check log_dir logs)")
                    try:
                        cli = CoordinatorClient(eport, timeout=2.0,
                                                retries=0)
                        ok = cli.ping()
                        cli.close()
                        if ok:
                            break
                    except OSError:
                        pass
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"fleet replica {name} not serving on "
                            f":{eport} within {spawn_timeout_s}s")
                    time.sleep(0.1)
                router.register(
                    name, RemoteEngineProxy(eport, token=token or None,
                                            **(proxy_kw or {})),
                    role=roles.get(name, "both"))
        except BaseException:
            handle.stop()             # SIGTERM spawned procs, close
            raise                     # logs, stop router + pollers
    else:
        for i, name in enumerate(names):
            router.register(name, build_engine(i),
                            role=roles.get(name, "both"))

    coordinator = None
    if port is not None:
        from hetu_tpu.rpc.py_server import PyCoordinatorServer
        coordinator = PyCoordinatorServer(port, bind=bind, token=token,
                                          serving=router)
        coordinator.start()
        coordinator.wait_ready()
    handle.coordinator = coordinator
    handle.port = port
    get_logger().info(
        f"serving fleet up: {n_replicas} "
        f"{'process' if remote else 'in-process'} replicas "
        f"({', '.join(names)})"
        + (f", coordinator :{port}" if port is not None else ""))
    return handle
