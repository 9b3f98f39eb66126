"""Elastic multi-host data-parallel training (parallel/elastic.py +
train/elastic.py).

The load-bearing property is MEMBERSHIP INVARIANCE: the virtual-shard step
protocol makes the training trajectory a function of (seed, data, vshards)
alone — never of which workers computed it — so an N-process run, a shrunken
survivor set, and a rejoined straggler must all land on the IDENTICAL final
params (bit-exact on CPU). Subprocess scenarios below drive the real CLI
(`python -m deeplearning4j_tpu.train.elastic launch`): parity, deterministic
kill-shrink-continue, kill-relaunch-rejoin, and corrupt-distributed-shard
fallback; in-process unit tests cover the store CRC framing, lease expiry,
the chaos grammar extensions, and checkpoint I/O retries.
"""

import contextlib
import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.parallel.elastic import (
    ElasticRuntime,
    FileStore,
    Membership,
    MembershipChanged,
    View,
)
from deeplearning4j_tpu.train import resilience
from deeplearning4j_tpu.train.resilience import ChaosInjector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one synthetic workload for every subprocess scenario: 6 steps over 24 rows
WORKLOAD = ["--epochs", "2", "--batch", "8", "--n", "24", "--features", "4",
            "--classes", "3", "--hidden", "8", "--lr", "5e-3", "--seed", "7",
            "--vshards", "2", "--poll", "0.02"]


def _launch(root, name, *, workers, world, chaos=None, relaunch=0,
            allow_failures=0, ckpt=None, ckpt_every=0, ttl=2.0, extra=(),
            store=None):
    """Run the elastic CLI launcher to completion; returns the out dir.
    ``store`` overrides the per-scenario FileStore directory (e.g. a
    ``tcp://host:port`` netstore spec)."""
    if store is None:
        store = os.path.join(root, name, "store")
        os.makedirs(store, exist_ok=True)
    out = os.path.join(root, name, "out")
    os.makedirs(out, exist_ok=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO
    if chaos:
        env["DL4J_TPU_CHAOS"] = chaos
    else:
        env.pop("DL4J_TPU_CHAOS", None)
    cmd = [sys.executable, "-m", "deeplearning4j_tpu.train.elastic",
           "launch", "--store", store, "--outdir", out,
           "--workers", str(workers), "--world", str(world),
           "--relaunch", str(relaunch),
           "--allow-failures", str(allow_failures),
           "--ttl", str(ttl), "--timeout", "240", *WORKLOAD, *extra]
    if ckpt:
        cmd += ["--ckpt-dir", ckpt, "--ckpt-every", str(ckpt_every)]
    r = subprocess.run(cmd, env=env, capture_output=True, timeout=300)
    assert r.returncode == 0, (
        f"launch {name} failed:\n{r.stdout.decode()[-3000:]}"
        f"\n{r.stderr.decode()[-2000:]}")
    return out


def _result(out, wid="w0"):
    with open(os.path.join(out, f"result_{wid}.json")) as f:
        return json.load(f)


def _params(out, wid="w0"):
    with np.load(os.path.join(out, f"params_{wid}.npz")) as z:
        return {k: z[k] for k in z.files}


def _assert_params_equal(a, b, msg):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{msg}: {k}")


@pytest.fixture(scope="module")
def baseline(tmp_path_factory):
    """The uninterrupted single-process reference run (vshards=2, so every
    elastic scenario below shares its virtual-shard geometry), plus its
    distributed checkpoint layout for the restart scenarios."""
    root = str(tmp_path_factory.mktemp("elastic"))
    ckpt = os.path.join(root, "ckpt2w")
    out1 = _launch(root, "ref", workers=1, world=1)
    # a clean 2-worker run WITH distributed checkpoints every 2 iterations
    # (feeds the corrupt-shard scenario)
    out2 = _launch(root, "ckptrun", workers=2, world=2, ckpt=ckpt,
                   ckpt_every=2)
    return {"root": root, "out1": out1, "out2": out2, "ckpt": ckpt}


# ---------------------------------------------------------------------------
# Subprocess scenarios
# ---------------------------------------------------------------------------


def test_two_worker_parity(baseline):
    """N-process data parallelism is bit-exact vs single-process: same loss
    curve, same final params, and both workers agree with each other."""
    ref = _result(baseline["out1"])
    got = _result(baseline["out2"], "w0")
    peer = _result(baseline["out2"], "w1")
    assert got["world"] == 2 and got["iteration"] == 6
    assert got["losses"] == ref["losses"]
    assert peer["losses"] == ref["losses"]
    _assert_params_equal(_params(baseline["out2"], "w0"),
                         _params(baseline["out1"]), "2-worker vs 1-worker")
    _assert_params_equal(_params(baseline["out2"], "w1"),
                         _params(baseline["out2"], "w0"), "worker disagree")


def test_kill_one_worker_shrinks_and_continues(baseline):
    """host_kill SIGKILLs rank 1 mid-epoch; the survivor detects the lapsed
    lease, re-forms at world 1 (re-sharding the optimizer segments from its
    buddy mirror) and finishes with the UNINTERRUPTED run's exact curve."""
    out = _launch(baseline["root"], "kill", workers=2, world=2,
                  chaos="host_kill@iter:3:rank1", allow_failures=1)
    ref = _result(baseline["out1"])
    got = _result(out, "w0")
    assert got["world"] == 1, "survivor should have shrunk to world 1"
    assert got["gen"] >= 1, "a shrink view must have been proposed"
    assert got["losses"] == ref["losses"]
    _assert_params_equal(_params(out, "w0"), _params(baseline["out1"]),
                         "post-shrink params")
    # membership telemetry: the survivor logged the shrink
    events = [json.loads(l)
              for l in open(os.path.join(out, "events_w0.jsonl"))]
    changes = [e for e in events if e["kind"] == "membership_change"]
    assert any(e["reason"] == "shrink" and e["removed"] == ["w1"]
               for e in changes), changes


def test_killed_worker_rejoins_bit_exact(baseline):
    """The launcher relaunches the killed worker; it re-leases under a new
    incarnation, the survivors grow the view back, and the handoff restores
    bit-exact state on BOTH workers (including the rejoined one).

    The survivor needs 2 s (the lease TTL) to shrink and a moment for the
    three steps that are left; a relaunched interpreter needs longer than
    that to import jax. So the survivor is held at iteration 4 (one stall,
    rank 0 only) until the newcomer has its lease: without it the job is
    over before anyone can rejoin, and nothing of the grow path runs."""
    out = _launch(baseline["root"], "rejoin", workers=2, world=2,
                  chaos="host_kill@iter:3:rank1,slow_iter@iter:4:rank0:15",
                  relaunch=1)
    ref = _result(baseline["out1"])
    for wid in ("w0", "w1"):
        got = _result(out, wid)
        assert got["world"] == 2, f"{wid} should end back at world 2"
        assert got["losses"] == ref["losses"]
        _assert_params_equal(_params(out, wid), _params(baseline["out1"]),
                             f"post-rejoin params ({wid})")
    events = [json.loads(l)
              for l in open(os.path.join(out, "events_w0.jsonl"))]
    reasons = [e["reason"] for e in events
               if e["kind"] == "membership_change"]
    assert "shrink" in reasons and "grow" in reasons, reasons


def test_corrupt_distributed_shard_falls_back_to_mirror(baseline):
    """Full-group restart from the distributed checkpoint layout with rank
    1's newest shard file corrupted: the loader drops it (CRC) and the
    trainer assembles rank 1's optimizer segments from rank 0's buddy
    mirror — restart still lands on the uninterrupted params."""
    ckpt = baseline["ckpt"]
    manifests = sorted(f for f in os.listdir(ckpt)
                       if f.startswith("manifest_"))
    assert manifests, "ckptrun produced no distributed checkpoints"
    tag = manifests[-1][len("manifest_"):-len(".json")]
    resilience.corrupt_file(os.path.join(ckpt, f"shard_{tag}_r1.npz"),
                            mode="bitflip")
    out = _launch(baseline["root"], "restart", workers=2, world=2,
                  ckpt=ckpt, ckpt_every=0)
    ref = _result(baseline["out1"])
    got = _result(out, "w0")
    assert got["losses"] == ref["losses"]
    _assert_params_equal(_params(out, "w0"), _params(baseline["out1"]),
                         "post-restart params")
    dropped = [l for w in ("w0", "w1")
               for l in open(os.path.join(out, f"events_{w}.jsonl"))
               if "checkpoint_shard_dropped" in l]
    assert dropped, "the corrupt shard should have been CRC-dropped"


@contextlib.contextmanager
def _net_server(root):
    """A netstore server in its own process; yields its tcp:// spec."""
    announce = os.path.join(root, "netstore.addr")
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "deeplearning4j_tpu.parallel.netstore",
         "serve", "--host", "127.0.0.1", "--port", "0",
         "--data", os.path.join(root, "netstore.data"),
         "--announce", announce],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 20.0
        while not os.path.exists(announce):
            assert proc.poll() is None, "netstore server died at startup"
            assert time.monotonic() < deadline, "server never announced"
            time.sleep(0.05)
        with open(announce) as f:
            yield "tcp://" + f.read().strip()
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_netstore_backend_end_to_end(baseline, tmp_path):
    """DL4J_TPU_STORE parity at the system level: the trainers run
    unmodified over the TCP store and land on the FileStore reference's
    exact curve and params."""
    with _net_server(str(tmp_path)) as spec:
        out = _launch(baseline["root"], "netrun", workers=2, world=2,
                      store=spec)
    ref = _result(baseline["out1"])
    got = _result(out, "w0")
    assert got["store_backend"] == "tcp"
    assert got["losses"] == ref["losses"]
    _assert_params_equal(_params(out, "w0"), _params(baseline["out1"]),
                         "netstore vs filestore params")


@pytest.mark.slow
def test_r3_survives_loss_of_two_mirrors(baseline):
    """R=3 mirror replication: slice_kill takes out ranks 1 AND 2 at the
    same boundary. Rank 0 holds a complete mirror set, rebuilds every
    segment locally, and finishes on the uninterrupted curve."""
    out = _launch(baseline["root"], "r3", workers=3, world=3,
                  chaos="slice_kill@iter:3:slice1,slice_kill@iter:3:slice2",
                  allow_failures=2, extra=("--replication", "3"))
    ref = _result(baseline["out1"])
    got = _result(out, "w0")
    assert got["world"] == 1 and got["replication"] == 3
    assert got["losses"] == ref["losses"]
    _assert_params_equal(_params(out, "w0"), _params(baseline["out1"]),
                         "post-double-kill params")


@pytest.mark.slow
def test_slice_members_bit_exact_across_member_count(baseline):
    """Members are 2-device mesh slices (slice-level membership): killing a
    whole slice shrinks the group, and the survivor matches a 1-slice run
    of the SAME slice shape — bit-exactness is across member count at fixed
    slice spec."""
    extra = ("--mesh", "2", "--slice-devices", "2")
    ref_out = _launch(baseline["root"], "slice_ref", workers=1, world=1,
                      extra=extra)
    out = _launch(baseline["root"], "slice_kill", workers=2, world=2,
                  chaos="slice_kill@iter:3:slice1", allow_failures=1,
                  extra=extra)
    ref = _result(ref_out)
    got = _result(out, "w0")
    assert got["world"] == 1
    assert got["losses"] == ref["losses"]
    _assert_params_equal(_params(out, "w0"), _params(ref_out),
                         "slice-kill survivor vs 1-slice reference")
    events = [json.loads(l)
              for l in open(os.path.join(out, "events_w1.jsonl"))]
    assert any(e["kind"] == "slice_kill" for e in events), \
        "the killed member should have logged the slice_kill fault"


@pytest.mark.slow
def test_rack_partition_shrinks_and_readmits_bit_exact(baseline):
    """rack_partition suspends every member whose rack label matches: w1
    (rackB) goes silent past the lease TTL, the group shrinks, the
    partition heals, w1 is readmitted, and BOTH workers finish on the
    uninterrupted curve."""
    out = _launch(baseline["root"], "rackpart", workers=2, world=2,
                  chaos="rack_partition@iter:3:rackB:1.0", ttl=1.0,
                  extra=("--racks", "rackA,rackB"))
    ref = _result(baseline["out1"])
    for wid in ("w0", "w1"):
        got = _result(out, wid)
        assert got["world"] == 2, f"{wid} should end back at world 2"
        assert got["losses"] == ref["losses"]
        _assert_params_equal(_params(out, wid), _params(baseline["out1"]),
                             f"post-rack-partition params ({wid})")
    assert _result(out, "w1")["rack"] == "rackB"
    events = [json.loads(l)
              for l in open(os.path.join(out, "events_w1.jsonl"))]
    phases = [e["phase"] for e in events if e["kind"] == "rack_partition"]
    assert phases == ["begin", "end"], phases


# ---------------------------------------------------------------------------
# Membership runtime units (in-process)
# ---------------------------------------------------------------------------


def test_filestore_crc_framing(tmp_path):
    store = FileStore(tmp_path)
    store.set("a/b", b"payload")
    assert store.get("a/b") == b"payload"
    assert store.get("missing") is None
    # flip a byte inside the framed file: CRC must reject, not return junk
    path = store._path("a/b")
    data = bytearray(open(path, "rb").read())
    data[-1] ^= 0x40
    open(path, "wb").write(bytes(data))
    assert store.get("a/b") is None


def test_filestore_exclusive_create(tmp_path):
    store = FileStore(tmp_path)
    assert store.set_exclusive("gen/1", b"first") is True
    assert store.set_exclusive("gen/1", b"second") is False
    assert store.get("gen/1") == b"first"


def test_lease_expiry_and_incarnation(tmp_path):
    store = FileStore(tmp_path)
    m = Membership(store, "w0", ttl=0.2, poll=0.05)
    m.join()
    try:
        inc1 = m.incarnation
        assert "w0" in m.live()
        m.suspend(10.0)  # stop heartbeating (the net_partition mechanism)
        deadline = time.monotonic() + 5.0
        while "w0" in m.live():
            assert time.monotonic() < deadline, "lease never expired"
            time.sleep(0.05)
        assert m.expired("w0")
    finally:
        m.leave()
    # a re-join is a NEW incarnation: the relaunched-process identity check
    m2 = Membership(store, "w0", ttl=0.2, poll=0.05)
    m2.join()
    try:
        assert m2.incarnation != inc1
    finally:
        m2.leave()


def test_view_holders_require_matching_incarnations():
    v = View(gen=3, members=("w0", "w1"), prev_members=("w0", "w1"),
             epoch=1, step=2, iteration=5, reason="grow", rejoined=(),
             incs={"w0": "a.1", "w1": "b.2"},
             prev_incs={"w0": "a.1", "w1": "b.STALE"})
    # w1's incarnation changed between views -> it is a joiner, NOT a state
    # holder (the relaunched-worker hazard)
    assert v.holders() == ("w0",)
    assert v.rank_of("w1") == 1
    rt = View.from_json(json.loads(json.dumps(v.to_json())))
    assert rt == v


def test_runtime_bootstrap_and_shrink(tmp_path):
    store = FileStore(tmp_path)
    a = ElasticRuntime(store, "a", ttl=0.3, poll=0.02)
    b = ElasticRuntime(store, "b", ttl=0.3, poll=0.02)
    try:
        views = {}
        tb = threading.Thread(
            target=lambda: views.setdefault("b", b.bootstrap(2, timeout=10)))
        tb.start()
        va = a.bootstrap(2, timeout=10)
        tb.join(timeout=10)
        assert va.members == ("a", "b") and va.gen == views["b"].gen
        # b dies; a reports it and shrinks
        b.membership.suspend(30.0)
        deadline = time.monotonic() + 5.0
        while True:
            try:
                a.poll_boundary((0, 0, 0))
                assert time.monotonic() < deadline, "shrink never proposed"
                time.sleep(0.05)
            except MembershipChanged as mc:
                assert mc.view.members == ("a",)
                assert mc.view.reason == "shrink"
                break
    finally:
        a.leave()
        b.leave()


# ---------------------------------------------------------------------------
# Chaos grammar + retry units
# ---------------------------------------------------------------------------


def test_chaos_grammar_host_kill_and_partition():
    inj = ChaosInjector.parse(
        "host_kill@iter:3:rank1,net_partition@iter:2:rank0:1.5")
    kinds = sorted(f.kind for f in inj.faults)
    assert kinds == ["host_kill", "net_partition"]
    hk = next(f for f in inj.faults if f.kind == "host_kill")
    assert hk.at_iter == 3 and hk.arg == "rank1"
    npf = next(f for f in inj.faults if f.kind == "net_partition")
    assert npf.at_iter == 2 and npf.arg == "rank0:1.5"
    assert ChaosInjector._rank_arg("rank1:4.0") == (1, "4.0")
    assert ChaosInjector._rank_arg("rank2") == (2, None)
    assert ChaosInjector._rank_arg("3.5") == (None, "3.5")
    assert ChaosInjector._rank_arg(None) == (None, None)


def test_chaos_host_kill_targets_rank_and_fires_once():
    inj = ChaosInjector.parse("host_kill@iter:3:rank1")
    # wrong rank: never fires regardless of iteration
    for it in range(10):
        inj.maybe_host_kill(it, rank=0)  # would SIGKILL us if it fired
    # partition: targeted, one-shot, carries its duration
    inj2 = ChaosInjector.parse("net_partition@iter:2:rank1:0.75")
    assert inj2.partition_seconds(1, rank=1) == 0.0
    assert inj2.partition_seconds(2, rank=0) == 0.0
    assert inj2.partition_seconds(2, rank=1) == 0.75
    assert inj2.partition_seconds(3, rank=1) == 0.0, "must be one-shot"
    # default duration
    inj3 = ChaosInjector.parse("net_partition@iter:0")
    assert inj3.partition_seconds(0, rank=4) == 5.0


def test_chaos_unknown_kind_still_rejected():
    with pytest.raises(ValueError, match="unknown kind"):
        ChaosInjector.parse("soft_kill@iter:3")


def test_chaos_grammar_slice_kill_and_rack_partition():
    inj = ChaosInjector.parse(
        "slice_kill@iter:3:slice1,rack_partition@iter:2:rackA:1.5")
    kinds = sorted(f.kind for f in inj.faults)
    assert kinds == ["rack_partition", "slice_kill"]
    sk = next(f for f in inj.faults if f.kind == "slice_kill")
    assert sk.at_iter == 3 and sk.arg == "slice1"
    # the generalized prefix splitter, and _rank_arg's exact legacy shape
    assert ChaosInjector._prefixed_arg("slice2", "slice") == (2, None)
    assert ChaosInjector._prefixed_arg("slice1:x", "slice") == (1, "x")
    assert ChaosInjector._prefixed_arg("rank1:4.0", "rank") == (1, "4.0")
    assert ChaosInjector._rank_arg("rank1:4.0") == (1, "4.0")
    assert ChaosInjector._rank_arg("3.5") == (None, "3.5")
    assert ChaosInjector._rank_arg(None) == (None, None)


def test_chaos_slice_kill_targets_slice_index():
    inj = ChaosInjector.parse("slice_kill@iter:3:slice1")
    for it in range(10):
        inj.maybe_slice_kill(it, slice_index=0)  # would SIGKILL if it fired


def test_chaos_rack_partition_matches_label():
    inj = ChaosInjector.parse("rack_partition@iter:2:rackB:0.75")
    assert inj.rack_partition_seconds(1, rack="rackB") == 0.0
    assert inj.rack_partition_seconds(2, rack="rackA") == 0.0, \
        "a non-matching rack label must not fire (or consume) the fault"
    assert inj.rack_partition_seconds(2, rack="rackB") == 0.75
    assert inj.rack_partition_seconds(3, rack="rackB") == 0.0, "one-shot"
    # bare seconds: every rack
    inj2 = ChaosInjector.parse("rack_partition@iter:0:1.25")
    assert inj2.rack_partition_seconds(0, rack="anything") == 1.25
    # no arg: default duration, every rack
    inj3 = ChaosInjector.parse("rack_partition@iter:0")
    assert inj3.rack_partition_seconds(0, rack="r") == 5.0


def test_mirror_ranks_rack_aware_placement():
    from deeplearning4j_tpu.train.elastic import mirror_ranks
    # R=2 with uniform racks IS the legacy buddy pair (checkpoint layout
    # and membership-invariance gates depend on this exact orientation)
    for W in range(2, 7):
        for t in range(W):
            assert mirror_ranks(t, W, 2, [""] * W) == [(t - 1) % W]
    # two racks: the mirror always lands outside the owner's rack
    racks = ["A", "A", "B", "B"]
    for t in range(4):
        (m,) = mirror_ranks(t, 4, 2, racks)
        assert racks[m] != racks[t]
    # R=3 over three racks: both mirrors land off-rack
    racks = ["A", "B", "C", "A", "B", "C"]
    for t in range(6):
        ms = mirror_ranks(t, 6, 3, racks)
        assert len(ms) == 2 and all(racks[m] != racks[t] for m in ms)
    # degenerate shapes: R caps at W, and a single member has no mirrors
    assert mirror_ranks(0, 2, 5, ["", ""]) == [1]
    assert mirror_ranks(0, 1, 3, [""]) == []
    assert mirror_ranks(2, 4, 1, [""] * 4) == []


def test_set_exclusive_o_excl_fallback(tmp_path, monkeypatch):
    """Filesystems without hardlinks (FAT, some NFS): set_exclusive falls
    back to an O_EXCL create — exclusivity preserved, one RuntimeWarning
    total, record still CRC-framed and readable."""
    import deeplearning4j_tpu.parallel.elastic as pe

    def no_link(src, dst):
        raise OSError(38, "Function not implemented")

    monkeypatch.setattr(os, "link", no_link)
    monkeypatch.setattr(pe, "_LINK_FALLBACK_WARNED", False)
    store = FileStore(tmp_path)
    with pytest.warns(RuntimeWarning, match="os.link unsupported"):
        assert store.set_exclusive("view/00000001", b"winner")
    assert not store.set_exclusive("view/00000001", b"loser")
    assert store.get("view/00000001") == b"winner"
    # warn-once: further fallbacks stay quiet
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert store.set_exclusive("view/00000002", b"x")


def test_membership_suspend_blocks_renewal(tmp_path):
    """suspend() and the heartbeat thread share a lock: no renewal may land
    during the suspension window, and heartbeat_now() lifts it."""
    store = FileStore(tmp_path)
    m = Membership(store, "w", ttl=0.4, poll=0.02)
    m.join()
    try:
        m.suspend(30.0)
        ts0 = m.lease("w")["ts"]
        time.sleep(0.6)
        lease = m.lease("w")
        assert lease["ts"] == ts0, "heartbeat renewed a suspended lease"
        assert not m._fresh(lease)
        m.heartbeat_now()
        assert m._fresh(m.lease("w"))
    finally:
        m.leave()
    assert m._thread is None, "leave() must reap the heartbeat thread"


def test_io_with_retries_backoff_and_counter(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_CKPT_RETRIES", "3")
    monkeypatch.setenv("DL4J_TPU_CKPT_RETRY_BASE_S", "0.0")
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    before = obs.counter("dl4j_ckpt_retries_total", "").value()
    assert resilience.io_with_retries(flaky, what="unit") == "ok"
    assert calls["n"] == 3
    assert obs.counter("dl4j_ckpt_retries_total", "").value() == before + 2

    def always():
        raise OSError("permanent")

    with pytest.raises(OSError, match="permanent"):
        resilience.io_with_retries(always, what="unit")


def test_write_bytes_durable_atomic(tmp_path):
    p = tmp_path / "blob.bin"
    resilience.write_bytes_durable(p, b"x" * 1000)
    assert p.read_bytes() == b"x" * 1000
    resilience.write_bytes_durable(p, b"y" * 10)
    assert p.read_bytes() == b"y" * 10
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_load_distributed_checkpoint_manifest_fallback(tmp_path):
    """A manifest whose params file fails CRC falls back to the next-older
    manifest; a corrupt shard inside a valid manifest is dropped alone."""
    d = str(tmp_path)

    def write_ckpt(tag, seed):
        rs = np.random.RandomState(seed)
        import io as _io

        def npz_bytes(**arrays):
            buf = _io.BytesIO()
            np.savez(buf, **arrays)
            return buf.getvalue()

        names = {}
        for r in range(2):
            name = f"shard_{tag}_r{r}.npz"
            resilience.write_bytes_durable(
                os.path.join(d, name),
                npz_bytes(**{f"k0_t{r}": rs.rand(2, 4)}))
            names[r] = name
        pname = f"ckpt_{tag}_params.npz"
        resilience.write_bytes_durable(
            os.path.join(d, pname), npz_bytes(p0_0=rs.rand(3)))
        man = {
            "format": 1, "tag": tag, "iteration": int(tag), "epoch": 0,
            "step": 0, "world": 2, "members": ["w0", "w1"], "vshards": 2,
            "params": {"file": pname,
                       "crc": resilience.crc32_file(os.path.join(d, pname)),
                       "size": os.path.getsize(os.path.join(d, pname))},
            "shards": {str(r): {
                "file": names[r],
                "crc": resilience.crc32_file(os.path.join(d, names[r])),
                "size": os.path.getsize(os.path.join(d, names[r])),
                "rank": r, "wid": f"w{r}"} for r in range(2)},
        }
        resilience.write_json_durable(
            os.path.join(d, f"manifest_{tag}.json"), man)

    write_ckpt("00000002", seed=1)
    write_ckpt("00000004", seed=2)
    got = resilience.load_distributed_checkpoint(d)
    assert got["manifest"]["tag"] == "00000004"
    assert sorted(got["shards"]) == [0, 1]
    # corrupt one shard of the newest: manifest still loads, shard dropped
    resilience.corrupt_file(os.path.join(d, "shard_00000004_r1.npz"))
    got = resilience.load_distributed_checkpoint(d)
    assert got["manifest"]["tag"] == "00000004"
    assert sorted(got["shards"]) == [0]
    # corrupt the newest params file: whole manifest falls back to older
    resilience.corrupt_file(os.path.join(d, "ckpt_00000004_params.npz"))
    got = resilience.load_distributed_checkpoint(d)
    assert got["manifest"]["tag"] == "00000002"
    # nothing valid -> None
    resilience.corrupt_file(os.path.join(d, "ckpt_00000002_params.npz"))
    assert resilience.load_distributed_checkpoint(d) is None


# ---------------------------------------------------------------------------
# Distributed .aotbundle layout
# ---------------------------------------------------------------------------


def test_distributed_bundle_paths_and_manifest(tmp_path):
    from deeplearning4j_tpu.nn import aot

    base = str(tmp_path / "ckpt_00000004")
    assert aot.distributed_bundle_path(base, 1).endswith(
        "ckpt_00000004_r1.aotbundle")
    # hand-written sidecars merge into {rank: entry}; garbage is dropped
    for r in range(2):
        with open(f"{base}_r{r}.aotmanifest.json", "w") as f:
            json.dump({"rank": r, "file": f"ckpt_00000004_r{r}.aotbundle",
                       "crc32": 123, "size": 1}, f)
    with open(f"{base}_r9.aotmanifest.json", "w") as f:
        f.write("{not json")
    man = aot.distributed_bundle_manifest(base)
    assert sorted(man) == [0, 1]
    # no bundle files on disk -> restore installs nothing, never raises
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerConfiguration

    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=4, activation="tanh"),
                OutputLayer(n_out=2, activation="softmax")),
        input_type=InputType.feed_forward(3),
        updater={"type": "sgd", "lr": 1e-2}, seed=1)
    model = MultiLayerNetwork(conf).init()
    assert aot.restore_distributed_bundle(model, base, 0) == 0
