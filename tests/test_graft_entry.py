"""``__graft_entry__`` must expose working entry points: the jittable
forward the driver compiles and the multichip dry run it validates."""

import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_graft_entry_fn_runs():
    import jax
    sys.path.insert(0, _ROOT)
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[1].shape[0]
    assert bool(jax.numpy.isfinite(out).all())


def test_dryrun_multichip_smoke():
    """The driver's multichip validation, in a FRESH process — exactly
    how the driver invokes it. (In-process after a long test session it
    deadlocks: accumulated executables starve the single-core CPU
    backend's collective rendezvous permanently — see
    cpu-collective-rendezvous notes; the driver never runs it that
    way.)"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # dryrun sets its own
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        capture_output=True, text=True, timeout=900, env=env, cwd=_ROOT)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert r.stdout.count(" ok") >= 10, r.stdout
