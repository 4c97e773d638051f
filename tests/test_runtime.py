"""repro.runtime (ISSUE 7): env bootstrap ordering, cluster launch
no-op/parsing, process primitives (locks, heartbeats, crash points),
shared-ledger stale-claim stealing, and the real multi-process
generation fleet — two OS processes racing the ledger produce a
manifest bitwise-identical to the in-process path, and survive a
SIGKILL mid-range."""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import warnings

import numpy as np
import pytest

from repro.pipeline.generate import (WorkLedger, generate_sharded,
                                     shard_ranges)
from repro.runtime import cluster, env, procs
from repro.store import LogitStoreV2

K, V = 4, 30


def _batches(n=7, b=2, t=5, f=8, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        out.append({
            "feats": rng.normal(size=(b, t, f)).astype(np.float32),
            "mask": np.ones((b, t), np.float32)})
    return out


PROBE = "repro.runtime.workers:linear_probe_engine"
PROBE_KW = {"k": K, "vocab": V, "seed": 3}


# ================================================================== env

def test_compose_xla_flags_idempotent_and_preserving():
    cfg = env.EnvConfig(host_device_count=8)
    once = env.compose_xla_flags("--some_other_flag=keep", cfg)
    assert "--some_other_flag=keep" in once
    assert "--xla_force_host_platform_device_count=8" in once
    twice = env.compose_xla_flags(once, cfg)
    assert twice == once                          # replace, not duplicate
    # a changed count replaces the old spelling in place
    re8to4 = env.compose_xla_flags(once, env.EnvConfig(host_device_count=4))
    assert re8to4.count("--xla_force_host_platform_device_count") == 1
    assert "=4" in re8to4 and "=8" not in re8to4


def test_bootstrap_writes_environ_dict():
    e = {}
    cfg = env.bootstrap(host_device_count=8, platform="gpu",
                        enable_x64=True, environ=e)
    assert cfg.host_device_count == 8
    assert env.forced_host_device_count(e) == 8
    assert e["JAX_PLATFORMS"] == "gpu"
    assert e["JAX_ENABLE_X64"] == "1"
    for flag in env.GPU_XLA_FLAGS:                # overlap flags applied
        assert flag in e["XLA_FLAGS"]


def test_bootstrap_cpu_skips_gpu_flags():
    e = {}
    env.bootstrap(host_device_count=2, platform="cpu", environ=e)
    assert "--xla_gpu" not in e["XLA_FLAGS"]


def test_bootstrap_after_jax_import_warns(monkeypatch):
    # jax is long imported in the test process: flag changes can't land.
    monkeypatch.setenv("XLA_FLAGS", "")           # restored on teardown
    assert "jax" in sys.modules
    with pytest.warns(RuntimeWarning, match="already imported"):
        env.bootstrap(host_device_count=4)


def test_envconfig_from_env_parsing():
    cfg = env.EnvConfig.from_env({
        "REPRO_HOST_DEVICES": "8", "REPRO_PLATFORM": "GPU",
        "REPRO_X64": "1", "REPRO_DEBUG_NANS": "no",
        "REPRO_XLA_FLAGS": "--a=1 --b=2"})
    assert cfg.host_device_count == 8
    assert cfg.platform == "gpu"
    assert cfg.enable_x64 is True
    assert cfg.debug_nans is False
    assert cfg.preallocate is None                # unset stays neutral
    assert cfg.extra_xla_flags == ("--a=1", "--b=2")
    neutral = env.EnvConfig.from_env({})
    assert neutral == env.EnvConfig()


def test_compile_cache_rule():
    """A JAX_COMPILATION_CACHE_DIR from outside is kept and nothing else
    is set; otherwise one fixed path inside the checkout, the same on
    every call (never a temp name, pid or time)."""
    outside = {"JAX_COMPILATION_CACHE_DIR": "/elsewhere/cache"}
    env.bootstrap_from_env(outside)
    assert outside == {"JAX_COMPILATION_CACHE_DIR": "/elsewhere/cache"}
    a, b = {}, {}
    env.bootstrap_from_env(a)
    env.bootstrap_from_env(b)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert a == b == {"JAX_COMPILATION_CACHE_DIR":
                      os.path.join(root, ".jax_cache")}


def test_auto_mesh_axes_are_auto():
    """Every mesh of the repo is Auto-typed (the installed jax defaults
    make_mesh to Explicit, under which the strategies' resharding
    raises)."""
    from jax.sharding import AxisType
    for mesh in (cluster.worker_mesh(2), cluster.auto_mesh((1, 1),
                                                           ("a", "b"))):
        assert set(mesh.axis_types) == {AxisType.Auto}


def test_forced_host_device_count_unforced():
    assert env.forced_host_device_count({}) == 0
    assert env.forced_host_device_count({"XLA_FLAGS": "--other=1"}) == 0


def test_describe_snapshot_keys(tmp_path):
    snap = env.save_describe(str(tmp_path / "env.json"))
    with open(tmp_path / "env.json") as f:
        assert json.load(f) == snap
    for key in ("jax_version", "backend", "device_count", "devices",
                "process_index", "process_count", "forced_host_devices",
                "xla_flags", "python", "pid"):
        assert key in snap, key
    assert snap["device_count"] == len(snap["devices"])


@pytest.mark.slow
def test_bootstrap_forces_device_count_in_fresh_interpreter():
    """The whole point of the subsystem: bootstrap *before* the first
    jax import yields a real N-device host-platform mesh."""
    code = textwrap.dedent("""
        from repro.runtime.env import bootstrap
        bootstrap(host_device_count=4)
        import jax
        assert len(jax.devices()) == 4, jax.devices()
        print("DEVICES", len(jax.devices()))
    """)
    ev = dict(procs.child_env())
    ev.pop("XLA_FLAGS", None)                     # a clean slate
    out = subprocess.run([sys.executable, "-c", code], env=ev,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "DEVICES 4" in out.stdout


# ============================================================== cluster

def test_widest_divisor():
    assert cluster.widest_divisor(16, 8) == 8
    assert cluster.widest_divisor(16, 5) == 4
    assert cluster.widest_divisor(7, 8) == 7
    assert cluster.widest_divisor(7, 3) == 1      # prime > devices
    assert cluster.widest_divisor(1, 64) == 1
    with pytest.raises(ValueError):
        cluster.widest_divisor(0, 8)


def test_worker_mesh_divides_worker_count():
    import jax
    for w in (1, 2, 3, 4, 16):
        mesh = cluster.worker_mesh(w)
        size = mesh.devices.size
        assert w % size == 0
        assert size <= len(jax.devices())
        assert mesh.axis_names == ("data",)


def test_topology_mesh_names():
    assert cluster.topology_mesh("gtc-16").axis_names == ("data",)
    with pytest.raises(KeyError):
        cluster.topology_mesh("bmuf-1024")


def test_cluster_config_from_spec():
    cfg = cluster.ClusterConfig.from_spec("host0:1234, 4, 2")
    assert cfg == cluster.ClusterConfig("host0:1234", 4, 2)
    env_cfg = cluster.ClusterConfig.from_spec(
        "env", environ={"REPRO_COORDINATOR": "c:1", "JAX_NUM_PROCESSES": "3",
                        "REPRO_PROCESS_ID": "1"})
    assert env_cfg == cluster.ClusterConfig("c:1", 3, 1)
    # REPRO_* wins over JAX_* when both are set
    both = cluster.ClusterConfig.from_env(
        {"REPRO_NUM_PROCESSES": "2", "JAX_NUM_PROCESSES": "9",
         "REPRO_COORDINATOR": "c:1"})
    assert both.num_processes == 2
    with pytest.raises(ValueError):
        cluster.ClusterConfig.from_spec("host:1,2")


def test_cluster_config_validate():
    cluster.ClusterConfig().validate()            # single-process: fine
    with pytest.raises(ValueError, match="coordinator"):
        cluster.ClusterConfig(num_processes=2).validate()
    with pytest.raises(ValueError, match="process_id"):
        cluster.ClusterConfig("c:1", 2, 5).validate()


def test_initialize_single_process_noop_and_idempotent():
    cluster._reset_for_tests()
    try:
        info = cluster.initialize(cluster.ClusterConfig())
        assert info == cluster.ClusterInfo(False, 0, 1)
        assert info.is_coordinator
        assert not info.initialized               # jax.distributed untouched
        # idempotent: a second call (even with a different cfg) returns
        # the recorded info instead of re-initializing
        again = cluster.initialize(
            cluster.ClusterConfig("c:1", 2, 1))
        assert again is info
        assert cluster.active() is info
    finally:
        cluster._reset_for_tests()


# ================================================================ procs

def test_file_lock_excludes_second_holder(tmp_path):
    lock = str(tmp_path / "x.lock")
    with procs.file_lock(lock):
        with pytest.raises(TimeoutError):
            with procs.file_lock(lock, timeout_s=0.2, poll_s=0.02):
                pass
    with procs.file_lock(lock, timeout_s=0.2):    # released: re-acquirable
        pass


def test_heartbeat_thread_and_age(tmp_path):
    hb = str(tmp_path / "hb")
    assert procs.heartbeat_age(hb, "w") is None   # never beat
    with procs.Heartbeat(hb, "w", interval_s=0.05):
        # first beat is synchronous in start()
        age0 = procs.heartbeat_age(hb, "w")
        assert age0 is not None and age0 < 1.0
        time.sleep(0.2)
    path = procs.heartbeat_path(hb, "w")
    past = time.time() - 60
    os.utime(path, (past, past))                  # silence the dead owner
    assert procs.heartbeat_age(hb, "w") > 30


def test_crash_point_disarmed_and_armed(tmp_path):
    cp = procs.CrashPoint(after=None)             # production default
    for _ in range(100):
        cp.tick()
    # armed: the (after+1)-th tick SIGKILLs — prove it on a subprocess
    code = ("from repro.runtime.procs import CrashPoint\n"
            "cp = CrashPoint(after=1)\n"
            "cp.tick(); print('one', flush=True)\n"
            "cp.tick()\n"
            "print('unreachable', flush=True)\n")
    out = subprocess.run([sys.executable, "-c", code],
                         env=procs.child_env(), capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == -signal.SIGKILL
    assert "one" in out.stdout and "unreachable" not in out.stdout


# ===================================================== shared-mode ledger

def _open_shared(tmp_path, n=4):
    path = str(tmp_path / "ledger.json")
    return WorkLedger.open(path, shard_ranges(8, n))


def test_reclaim_stale_by_heartbeat_age(tmp_path):
    led = _open_shared(tmp_path)
    procs.beat(led.heartbeat_dir, "a")
    claim = led.claim_shared("a")
    assert claim is not None
    # fresh heartbeat: nothing to steal
    assert led.reclaim_stale(max_age_s=5.0) == []
    # age the heartbeat past the timeout: the claim comes back
    hb = procs.heartbeat_path(led.heartbeat_dir, "a")
    past = time.time() - 60
    os.utime(hb, (past, past))
    stolen = led.reclaim_stale(max_age_s=5.0)
    assert [(r.lo, r.hi) for r in stolen] == [(claim.lo, claim.hi)]
    led.refresh()
    assert led.ranges[0].status == "pending"
    # the range is claimable again by a rival
    assert led.claim_shared("b") is not None


def test_reclaim_stale_never_beat_ages_the_claim(tmp_path):
    """A worker that died before its first beat has no heartbeat file:
    the claim's own timestamp ages it into stealability."""
    led = _open_shared(tmp_path)
    led.claim_shared("ghost")                     # no beat ever
    assert led.reclaim_stale(max_age_s=5.0) == []           # too young
    stolen = led.reclaim_stale(max_age_s=5.0, now=time.time() + 60)
    assert len(stolen) == 1


def test_reclaim_stale_owner_fast_path(tmp_path):
    """The supervisor's dead-child path: reclaim by exact owner, no
    heartbeat-age wait — and other owners' fresh claims are untouched."""
    led = _open_shared(tmp_path)
    procs.beat(led.heartbeat_dir, "dead")
    procs.beat(led.heartbeat_dir, "live")
    led.claim_shared("dead")
    keep = led.claim_shared("live")
    stolen = led.reclaim_stale(max_age_s=0.0, owners=["dead"])
    assert len(stolen) == 1 and stolen[0].owner == "dead"
    led.refresh()
    by_range = {(r.lo, r.hi): r for r in led.ranges}
    assert by_range[(keep.lo, keep.hi)].status == "claimed"
    assert by_range[(keep.lo, keep.hi)].owner == "live"


def test_mark_done_shared_idempotent_and_strict(tmp_path):
    led = _open_shared(tmp_path)
    claim = led.claim_shared("a")
    led.mark_done_shared(claim)
    led.mark_done_shared(claim)                   # stolen-and-finished twice
    led.refresh()
    assert led.n_done == 1
    from repro.pipeline.generate import WorkRange
    with pytest.raises(ValueError):
        led.mark_done_shared(WorkRange(100, 200))


def test_two_processes_race_claims_disjointly(tmp_path):
    """Two real OS processes hammer claim_shared on one ledger file:
    every range is claimed exactly once across both (the flock
    serializes the read-modify-write)."""
    path = str(tmp_path / "ledger.json")
    WorkLedger.open(path, shard_ranges(12, 12))
    code = textwrap.dedent("""
        import json, sys
        from repro.pipeline.generate import WorkLedger
        led = WorkLedger.attach(sys.argv[1])
        owner, out = sys.argv[2], []
        while True:
            c = led.claim_shared(owner)
            if c is None:
                break
            out.append([c.lo, c.hi])
            led.mark_done_shared(c)
        json.dump(out, open(sys.argv[3], "w"))
    """)
    ps = [subprocess.Popen(
        [sys.executable, "-c", code, path, f"p{i}",
         str(tmp_path / f"claims{i}.json")],
        env=procs.child_env()) for i in range(2)]
    for p in ps:
        assert p.wait(timeout=60) == 0
    claims = []
    for i in range(2):
        with open(tmp_path / f"claims{i}.json") as f:
            claims.append([tuple(c) for c in json.load(f)])
    merged = sorted(claims[0] + claims[1])
    assert merged == shard_ranges(12, 12)         # disjoint and complete
    led = WorkLedger.attach(path)
    assert led.all_done


# ==================================================== the process fleet

def _reference_manifest(tmp_path, batches):
    """The in-process manifest the fleet must reproduce byte-for-byte."""
    store = LogitStoreV2(str(tmp_path / "ref"), k=K, vocab=V)
    generate_sharded(PROBE, batches, store, n_workers=2,
                     engine_kwargs=PROBE_KW)
    with open(os.path.join(store.root, "manifest.json"), "rb") as f:
        return f.read()


def test_two_process_generation_bitwise_manifest(tmp_path):
    """generate_sharded(processes=2): two real worker processes race the
    ledger and the resulting manifest is bitwise identical to the
    in-process path."""
    batches = _batches(7)
    ref = _reference_manifest(tmp_path, batches)

    store = LogitStoreV2(str(tmp_path / "fleet"), k=K, vocab=V)
    rep = generate_sharded(PROBE, batches, store, n_workers=2,
                           engine_kwargs=PROBE_KW, processes=2,
                           supervisor_opts={"timeout_s": 90.0})
    assert rep["n_written"] == 7 and rep["processes"] == 2
    with open(os.path.join(store.root, "manifest.json"), "rb") as f:
        assert f.read() == ref
    assert store.verify() == 7                    # checksums intact
    assert store.gc() == []                       # no orphans left behind


def test_sigkill_mid_range_survivor_completes(tmp_path):
    """Chaos pin: worker 0 is SIGKILLed after its first shard write
    (mid-range, holding a claim).  The supervisor reclaims by owner,
    respawns, and the wave completes — with the manifest still bitwise
    identical to the in-process reference."""
    batches = _batches(8)
    ref = _reference_manifest(tmp_path, batches)

    store = LogitStoreV2(str(tmp_path / "fleet"), k=K, vocab=V)
    rep = generate_sharded(
        PROBE, batches, store, n_workers=2, engine_kwargs=PROBE_KW,
        processes=2, crash={"worker": 0, "after_shards": 1},
        supervisor_opts={"heartbeat_timeout_s": 1.0, "timeout_s": 90.0})
    assert rep["restarts"] >= 1                   # a replacement spawned
    assert rep["reclaimed"] >= 1                  # the orphaned claim stolen
    assert rep["n_written"] == 8
    with open(os.path.join(store.root, "manifest.json"), "rb") as f:
        assert f.read() == ref
    assert store.verify() == 8
    assert store.gc() == []


def test_processes_requires_engine_spec(tmp_path):
    store = LogitStoreV2(str(tmp_path / "s"), k=K, vocab=V)
    with pytest.raises(ValueError, match="module:function"):
        generate_sharded(lambda w: None, _batches(2), store, processes=2)


def test_process_fleet_refused_when_parent_holds_accelerator(
        tmp_path, monkeypatch):
    """One process per chip: a parent whose JAX backend is an
    accelerator must refuse to start generation workers (they would
    contend for its device) — loudly, before any child exists."""
    import jax
    spawned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "Popen",
                        lambda *a, **k: spawned.append(a))
    store = LogitStoreV2(str(tmp_path / "s"), k=K, vocab=V)
    with pytest.raises(RuntimeError, match="one process per chip"):
        generate_sharded(PROBE, _batches(2), store, n_workers=2,
                         engine_kwargs=PROBE_KW, processes=2)
    assert spawned == []
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    procs.refuse_children_on_accelerator("host fleet")   # CPU passes


def test_save_load_batches_roundtrip(tmp_path):
    from repro.runtime.workers import load_batches, save_batches
    batches = _batches(3)
    path = save_batches(str(tmp_path / "b.npz"), batches)
    back = load_batches(path)
    assert len(back) == 3
    for a, b in zip(batches, back):
        assert sorted(a) == sorted(b)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key])


def test_worker_blind_engine_factory():
    """The determinism precondition for claim stealing: the probe
    engine's output is identical no matter which worker built it."""
    from repro.runtime.workers import linear_probe_engine
    batch = _batches(1)[0]
    v0, i0 = linear_probe_engine(0, PROBE_KW).forward_topk(batch)
    v7, i7 = linear_probe_engine(7, PROBE_KW).forward_topk(batch)
    np.testing.assert_array_equal(v0, v7)
    np.testing.assert_array_equal(i0, i7)
