"""Fault tolerance of the tiered synapse memory (ISSUE 8).

The resilience contract, asserted here end to end:

* INTEGRITY — every cold read verifies the framed blob's checksum: a torn
  write, truncated file, or flipped bit surfaces as a typed
  `SnapshotLostError` (and the bad file moves to ``quarantine/``), never a
  raw codec exception or — worse — silently wrong cache bytes;
* RECOVERY — kill-and-restart: hibernate agents to cold, drop every piece
  of process state, `recover()` + `adopt_hibernated()` in a fresh engine,
  and the woken streams replay BITWISE vs an engine that never crashed
  (single-device and forced-8-device lane mesh);
* RETRY — transient I/O failures retry with bounded backoff and succeed;
  exhausted retries / deadlines / a dead prefetch worker fail the
  `WakeTicket` terminally (never hang a waiter) while the snapshot stays
  intact and re-wakeable; permanent loss marks the agent LOST, frees no
  lane, and the engine keeps ticking with every hot-path invariant (one
  sync per window, dispatch counts, zero-transfer overlap region) intact
  and untouched lanes bitwise identical to a fault-free run;
* CONCURRENCY — put/prefetch/drop/demote churn from many threads leaves no
  deadlock, no orphaned ``.tmp``/blob files, and exact tier accounting;
  the old get_host/drop race resolves to the key's current state instead
  of leaking ``FileNotFoundError``.
"""
import dataclasses
import os
import pickle
import threading
import time

import jax
import numpy as np
import pytest

from repro.checkpoint import io as ckpt_io
from repro.configs import get_config
from repro.core.engine import CortexEngine
from repro.core.prism import Prism
from repro.data.tokenizer import ByteTokenizer
from repro.launch.mesh import make_lane_mesh
from repro.memory import (
    ACTIVE,
    HIBERNATED,
    LOST,
    FaultInjector,
    SnapshotLostError,
    SynapseStore,
    WorkerDiedError,
)
from repro.models import model as model_lib
from repro.serving.sampler import SamplingParams
from repro.serving.server import BatchServer

N_DEV = jax.device_count()
needs_mesh = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

PROMPT_A = "calm text with no tags at all"
PROMPT_B = "another quiet prompt, still tagless"


def _cfg():
    return dataclasses.replace(
        get_config("qwen2.5-0.5b", reduced=True), compute_dtype="float32"
    )


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, *, n_main=2, max_side=2, sync_every=4, mesh=None,
            store=None, wake_deadline_s=None):
    return CortexEngine(
        Prism(params, cfg), ByteTokenizer(cfg.vocab_size), n_main=n_main,
        max_side=max_side, main_capacity=128, side_max_steps=50,
        inject_tokens=8, theta=-1.0, sampling=SamplingParams(greedy=True),
        sync_every=sync_every, mesh=mesh, store=store,
        wake_deadline_s=wake_deadline_s,
    )


def _tree_equal_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _snap(seed, kb=4):
    rng = np.random.default_rng(seed)
    return {
        "caches": rng.standard_normal(kb * 256).astype(np.float32),
        "tok": np.int32(seed),
        "pos": np.int64(seed * 10),
    }


def _cold_store(tmp_path, **kw):
    """warm_capacity_bytes=1 forces every put straight through to disk."""
    kw.setdefault("wake_backoff_s", 0.001)
    return SynapseStore(warm_capacity_bytes=1, cold_dir=str(tmp_path), **kw)


# ---------------------------------------------------------------------------
# framed blob format: integrity detection at the codec layer
# ---------------------------------------------------------------------------

def test_framed_roundtrip_bitwise_with_meta():
    tree = _snap(7)
    meta = pickle.dumps({"key": "x", "n": 3})
    blob = ckpt_io.dumps_framed(tree, meta=meta)
    hdr = ckpt_io.parse_frame_header(blob)
    assert hdr["codec"] in (ckpt_io.CODEC_ZLIB, ckpt_io.CODEC_ZSTD)
    got_meta, _, _ = ckpt_io.unframe(blob)
    assert got_meta == meta
    skel = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), tree
    )
    _tree_equal_bitwise(tree, ckpt_io.loads_framed(blob, skel, numpy=True))


def test_framed_catches_truncation_everywhere():
    blob = ckpt_io.dumps_framed(_snap(1), meta=b"m" * 17)
    for cut in (0, 3, ckpt_io.FRAME_HEADER_BYTES - 1, ckpt_io.FRAME_HEADER_BYTES,
                ckpt_io.FRAME_HEADER_BYTES + 5, len(blob) // 2, len(blob) - 1):
        with pytest.raises(ckpt_io.CorruptBlobError):
            ckpt_io.unframe(blob[:cut])
    with pytest.raises(ckpt_io.CorruptBlobError):  # oversize too
        ckpt_io.unframe(blob + b"x")


def test_framed_catches_every_single_bit_flip():
    """Flip one bit at EVERY byte offset: either verification raises
    CorruptBlobError, or (for bits the digest doesn't guard, e.g. inside
    the reserved header byte) decode still returns the original bytes —
    silent wrong data is never possible."""
    tree = _snap(2, kb=1)
    skel = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), tree
    )
    blob = ckpt_io.dumps_framed(tree, meta=b"bookkeeping")
    for i in range(len(blob)):
        bad = bytearray(blob)
        bad[i] ^= 0x01
        try:
            got = ckpt_io.loads_framed(bytes(bad), skel, numpy=True)
        except ckpt_io.CorruptBlobError:
            continue
        _tree_equal_bitwise(tree, got)  # e.g. the reserved byte: harmless


def test_read_frame_meta_cheap_and_checked(tmp_path):
    meta = pickle.dumps({"skeleton": "here"})
    blob = ckpt_io.dumps_framed(_snap(3), meta=meta)
    p = tmp_path / "x.blob"
    p.write_bytes(blob)
    assert ckpt_io.read_frame_meta(str(p)) == meta
    p.write_bytes(blob[: len(blob) - 10])  # truncated payload: size check fires
    with pytest.raises(ckpt_io.CorruptBlobError):
        ckpt_io.read_frame_meta(str(p))


# ---------------------------------------------------------------------------
# store: quarantine, retry/backoff, deadlines, worker supervision
# ---------------------------------------------------------------------------

def test_corrupt_cold_blob_quarantined(tmp_path):
    store = _cold_store(tmp_path, faults=FaultInjector().flip_write("a"))
    snap = _snap(1)
    store.put("a", snap)
    store.put("b", snap)  # written clean: must survive its neighbor's loss
    assert store.tier_of("a") == "cold"
    with pytest.raises(SnapshotLostError):
        store.get_host("a")
    assert store.tier_of("a") is None
    qdir = tmp_path / "quarantine"
    assert [p.name for p in qdir.iterdir()] and store.stats["quarantined"] == 1
    with pytest.raises(KeyError):  # follow-up access: plain miss, not loss
        store.get_host("a")
    _tree_equal_bitwise(snap, store.get_host("b"))


def test_torn_write_detected(tmp_path):
    store = _cold_store(tmp_path, faults=FaultInjector().torn_write("a", frac=0.6))
    store.put("a", _snap(1))
    with pytest.raises(SnapshotLostError):
        store.get_host("a")
    assert store.stats["lost"] == 1


def test_transient_read_failures_retry_through(tmp_path):
    store = _cold_store(tmp_path, faults=FaultInjector().fail_read("a", times=2))
    snap = _snap(4)
    store.put("a", snap)
    ticket = store.prefetch("a")
    _tree_equal_bitwise(snap, ticket.result(timeout=30))
    assert store.stats["wake_retries"] == 2
    assert store.stats["prefetch_errors"] == 0


def test_exhausted_retries_fail_ticket_terminally(tmp_path):
    store = _cold_store(tmp_path, faults=FaultInjector().fail_read("a", times=99))
    store.put("a", _snap(4))
    ticket = store.prefetch("a", retries=2)
    with pytest.raises(OSError):
        ticket.result(timeout=30)
    assert ticket.failed() and ticket.state == "failed"
    assert store.stats["prefetch_errors"] == 1
    assert store.stats["wake_retries"] == 2
    assert "a" in store  # the snapshot itself is intact: retryable later


def test_ticket_result_timeout_does_not_fail_ticket(tmp_path):
    """`result(timeout=)` expiry is the CALLER's timeout, not the ticket's:
    the promotion keeps going and can still succeed afterward."""
    store = _cold_store(
        tmp_path, faults=FaultInjector().slow_put("a", seconds=0.3)
    )
    snap = _snap(5)
    store.put("a", snap)
    ticket = store.prefetch("a", put_fn=lambda h: h)
    with pytest.raises(TimeoutError):
        ticket.result(timeout=0.01)
    assert not ticket.ready()  # still in flight, not failed
    _tree_equal_bitwise(snap, ticket.result(timeout=30))


def test_deadline_expires_blocked_promotion(tmp_path):
    """A worker stuck in put_fn cannot outlive the ticket deadline: the
    host expires the ticket (terminal TimeoutError) and the worker's late
    resolve loses the first-wins race — no crash, no hang."""
    release = threading.Event()
    store = _cold_store(
        tmp_path, faults=FaultInjector().block_put("a", release=release, timeout=30)
    )
    store.put("a", _snap(6))
    ticket = store.prefetch("a", put_fn=lambda h: h, deadline_s=0.05)
    deadline = time.monotonic() + 30
    while not ticket.ready() and time.monotonic() < deadline:
        ticket.expire()
        time.sleep(0.01)
    assert ticket.failed() and isinstance(ticket.error, TimeoutError)
    release.set()  # un-stick the worker; its resolve must be a no-op
    time.sleep(0.2)
    assert ticket.failed() and isinstance(ticket.error, TimeoutError)
    with pytest.raises(TimeoutError):
        ticket.result()
    # the worker survived (nothing raised through its loop): next wake works
    store.faults = None
    assert store.prefetch("a").result(timeout=30) is not None


@pytest.mark.filterwarnings("ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_worker_death_detected_and_healed(tmp_path):
    store = _cold_store(tmp_path, faults=FaultInjector().kill_worker_on_read("a"))
    snap = _snap(7)
    store.put("a", snap)
    ticket = store.prefetch("a")
    deadline = time.monotonic() + 30
    while store._worker.is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not store._worker.is_alive()
    assert store.heal_worker() == 1  # fails the orphaned in-flight ticket
    assert ticket.failed() and isinstance(ticket.error, WorkerDiedError)
    assert store.stats["worker_respawns"] == 1
    assert store.stats["prefetch_errors"] == 1
    # the respawned worker drains new tickets normally
    store.faults = None
    _tree_equal_bitwise(snap, store.prefetch("a").result(timeout=30))
    assert store.heal_worker() == 0  # healthy worker: supervision is a no-op


def test_get_host_drop_race_resolves_to_current_state(tmp_path):
    """Deterministic reproduction of the old race: the blob file vanishes
    between the index lookup and the read. A concurrent drop() must surface
    as a clean KeyError; a concurrent re-put() must return the NEW bytes;
    only a file missing with its index entry still live is a loss."""
    snap_old, snap_new = _snap(8), _snap(9)

    class RaceHook:
        def __init__(self, store, action):
            self.store, self.action, self.fired = store, action, False

        def on_cold_write(self, key, blob):
            return blob

        def on_put_fn(self, key):
            pass

        def on_cold_read(self, key, data):
            if not self.fired:
                self.fired = True
                self.action(self.store, key)  # mutate AFTER the file read...
                raise FileNotFoundError(key)  # ...and pretend the read lost
            return data

    # concurrent drop -> clean KeyError (the satellite's exact scenario)
    s1 = _cold_store(tmp_path / "d")
    s1.put("k", snap_old)
    s1.faults = RaceHook(s1, lambda st, k: st.drop(k))
    with pytest.raises(KeyError) as ei:
        s1.get_host("k")
    assert not isinstance(ei.value, SnapshotLostError)
    assert s1.stats["lost"] == 0 and s1.stats["quarantined"] == 0

    # concurrent re-put -> the new warm copy, not FileNotFoundError
    s2 = _cold_store(tmp_path / "r")
    s2.put("k", snap_old)
    s2.faults = RaceHook(s2, lambda st, k: st.put(k, snap_new))
    got = s2.get_host("k")
    _tree_equal_bitwise(
        {k: np.asarray(v) for k, v in snap_new.items()}, got
    )

    # file gone while still indexed -> permanent loss, index cleaned
    s3 = _cold_store(tmp_path / "l")
    s3.put("k", snap_old)
    os.remove(s3._cold_path("k"))
    with pytest.raises(SnapshotLostError):
        s3.get_host("k")
    assert "k" not in s3 and s3.stats["lost"] == 1


def test_concurrent_store_churn_no_orphans(tmp_path):
    """Satellite: hammer put/prefetch/drop/demote_lru from threads. No
    deadlock (bounded join), no orphaned .tmp/blob files, and the final
    report must account for exactly the keys that remain."""
    one = sum(np.asarray(x).nbytes for x in jax.tree.leaves(_snap(0)))
    store = SynapseStore(
        warm_capacity_bytes=3 * one, cold_dir=str(tmp_path), wake_backoff_s=0.001
    )
    snaps = {f"k{i}": _snap(i) for i in range(8)}
    stop = time.monotonic() + 3.0
    errors = []

    def churn(tid):
        rng = np.random.default_rng(tid)
        try:
            while time.monotonic() < stop:
                key = f"k{int(rng.integers(8))}"
                op = int(rng.integers(4))
                if op == 0:
                    store.put(key, snaps[key])
                elif op == 1:
                    try:
                        t = store.prefetch(key)
                        t.result(timeout=0.02)  # expiry path exercised too
                    except (KeyError, TimeoutError, OSError):
                        pass
                elif op == 2:
                    store.drop(key)
                else:
                    store.demote_lru()
        except Exception as e:  # anything else is a real bug
            errors.append(e)

    threads = [threading.Thread(target=churn, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "churn thread deadlocked"
    assert not errors, errors

    # drain the prefetch queue so no writer races the audit below
    store.heal_worker()
    for key in list(store.keys()):
        try:
            store.prefetch(key).result(timeout=30)
        except KeyError:
            pass
    # accounting: the report matches the index, the index matches the disk
    rep = store.report()
    keys = store.keys()
    assert rep["n_warm"] + rep["n_cold"] == len(keys)
    assert rep["warm_bytes"] == one * rep["n_warm"]
    on_disk = {p.name for p in tmp_path.iterdir()
               if p.name not in ("MANIFEST.pkl", "quarantine")}
    assert not {n for n in on_disk if n.endswith(".tmp")}, "orphaned tmp files"
    indexed = {os.path.basename(store._cold[k].path) for k in store._cold}
    assert on_disk == indexed, (on_disk, indexed)
    # every survivor still round-trips bitwise
    for key in keys:
        _tree_equal_bitwise(snaps[key], store.get_host(key))


# ---------------------------------------------------------------------------
# crash recovery: manifest + blob-embedded metadata
# ---------------------------------------------------------------------------

def test_recover_rebuilds_index_and_skeletons(tmp_path):
    store = _cold_store(tmp_path)
    snaps = {k: _snap(i) for i, k in enumerate(("alpha", "beta"))}
    for k, s in snaps.items():
        store.put(k, s, meta={"kind": "main", "tag": k})
    del store  # process death: only the directory survives

    fresh = SynapseStore(warm_capacity_bytes=1)
    report = fresh.recover(str(tmp_path))
    assert sorted(report["recovered"]) == ["alpha", "beta"]
    assert not report["quarantined"] and not report["lost"]
    assert fresh.stats["recovered"] == 2
    for k, s in snaps.items():
        assert fresh.tier_of(k) == "cold"
        assert fresh.meta_of(k) == {"kind": "main", "tag": k}
        _tree_equal_bitwise(s, fresh.get_host(k))


def test_recover_adopts_orphans_and_survives_bad_manifest(tmp_path):
    store = _cold_store(tmp_path)
    store.put("a", _snap(1), meta={"kind": "main"})
    store.put("b", _snap(2), meta={"kind": "main"})
    # crash before the manifest caught up: garbage manifest, blobs intact
    (tmp_path / "MANIFEST.pkl").write_bytes(b"not a pickle at all")
    fresh = SynapseStore(warm_capacity_bytes=1)
    report = fresh.recover(str(tmp_path))
    assert report["manifest_corrupt"]
    assert sorted(report["recovered"]) == ["a", "b"]
    assert sorted(report["orphans_adopted"]) == ["a", "b"]
    # and recover() rewrote a good manifest: a second restart is fast-path
    again = SynapseStore(warm_capacity_bytes=1)
    r2 = again.recover(str(tmp_path))
    assert sorted(r2["recovered"]) == ["a", "b"] and not r2["orphans_adopted"]


def test_recover_quarantines_corrupt_counts_missing(tmp_path):
    store = _cold_store(tmp_path)
    for k in ("good", "torn", "gone"):
        store.put(k, _snap(hash(k) % 100), meta={"kind": "main"})
    good_snap = store.get_host("good")
    # mangle the survivors: "torn" loses its payload tail, "gone" vanishes
    torn_path = store._cold_path("torn")
    blob = open(torn_path, "rb").read()
    open(torn_path, "wb").write(blob[: len(blob) // 2])
    os.remove(store._cold_path("gone"))
    del store

    fresh = SynapseStore(warm_capacity_bytes=1)
    report = fresh.recover(str(tmp_path))
    assert report["recovered"] == ["good"]
    assert len(report["quarantined"]) == 1 and report["lost"] == ["gone"]
    assert fresh.stats["quarantined"] == 1 and fresh.stats["lost"] == 1
    assert (tmp_path / "quarantine").exists()
    _tree_equal_bitwise(good_snap, fresh.get_host("good"))


# ---------------------------------------------------------------------------
# engine: kill-and-restart bitwise replay
# ---------------------------------------------------------------------------

def _run_kill_restart(cfg, params, mesh=None):
    # side lanes shard over the mesh: max_side must be a lane-axis multiple
    n_side = mesh.shape["lane"] if mesh is not None else 2
    # reference: same schedule, process never dies
    ref = _engine(cfg, params, mesh=mesh, max_side=n_side)
    ref.submit(PROMPT_A, lane=0, agent_id="alice")
    ref.submit(PROMPT_B, lane=1, agent_id="bob")
    ref.run(12)
    ref.hibernate("alice")
    ref.run(8)
    ref.wake("alice", wait=True)
    ref.run(12)
    ref_alice = next(m for m in ref.mains if m.agent_id == "alice")

    import tempfile

    cold_dir = tempfile.mkdtemp(prefix="resil_restart_")
    store = _cold_store(cold_dir)
    e1 = _engine(cfg, params, mesh=mesh, max_side=n_side, store=store)
    e1.submit(PROMPT_A, lane=0, agent_id="alice")
    e1.submit(PROMPT_B, lane=1, agent_id="bob")
    e1.run(12)
    e1.hibernate("alice")
    assert store.tier_of("alice") == "cold"
    del e1, store  # CRASH: every piece of process state is gone

    store2 = _cold_store(cold_dir)
    report = store2.recover(cold_dir)
    assert report["recovered"] == ["alice"]
    e2 = _engine(cfg, params, mesh=mesh, max_side=n_side, store=store2)
    adopted = e2.adopt_hibernated()
    assert adopted == ["alice"]
    rec = e2.registry.get("alice")
    assert rec.status == HIBERNATED
    assert e2.stats["recoveries"] == 1
    # bob never hibernated: his stream replays from scratch post-restart
    e2.submit(PROMPT_B, lane=1, agent_id="bob")
    e2.run(20)
    e2.wake("alice", wait=True)
    e2.run(12)
    alice2 = next(m for m in e2.mains if m.active and m.agent_id == "alice")
    # BITWISE: token ids, not just text
    assert alice2.tokens == ref_alice.tokens
    assert alice2.text == ref_alice.text
    # sampling params survived the crash too
    assert e2._main_sp[alice2.lane] == SamplingParams(greedy=True)


def test_kill_and_restart_replays_bitwise(setup):
    cfg, params = setup
    _run_kill_restart(cfg, params)


@needs_mesh
def test_kill_and_restart_replays_bitwise_on_mesh(setup):
    cfg, params = setup
    _run_kill_restart(cfg, params, mesh=make_lane_mesh(8))


def test_recovered_router_tail_still_matches_split_tag(setup):
    """A trigger tag split across the hibernate boundary must still fire
    after kill-and-restart: the router tail rides the blob metadata."""
    cfg, params = setup
    import tempfile

    cold_dir = tempfile.mkdtemp(prefix="resil_tail_")
    store = _cold_store(cold_dir)
    eng = _engine(cfg, params, store=store)
    eng.submit(PROMPT_A, lane=0, agent_id="alice")
    eng.run(8)
    # half a tag into the router, as a drain would leave it
    eng.router.feed("alice", "some text then [TA")
    eng.hibernate("alice")
    del eng, store

    store2 = _cold_store(cold_dir)
    store2.recover(cold_dir)
    e2 = _engine(cfg, params, store=store2)
    assert e2.adopt_hibernated() == ["alice"]
    trigs = e2.router.feed("alice", "SK: resume work] more text")
    assert [t.kind for t in trigs] == ["task"]
    assert trigs[0].payload == "resume work"


# ---------------------------------------------------------------------------
# engine: graceful degradation under injected faults
# ---------------------------------------------------------------------------

def test_permanent_loss_degrades_lost_engine_keeps_ticking(setup):
    """Corrupt blob at wake: the agent goes LOST, its would-be lane stays
    free, the OTHER lane's stream is bitwise identical to a fault-free
    engine, and the hot-path invariants (dispatch counts, one sync per
    window, zero transfers in the overlap region) hold throughout."""
    cfg, params = setup
    ref = _engine(cfg, params)
    ref.submit(PROMPT_A, lane=0, agent_id="alice")
    ref.submit(PROMPT_B, lane=1, agent_id="bob")
    ref.run(32)
    ref_bob = next(m for m in ref.mains if m.agent_id == "bob")

    import tempfile

    store = _cold_store(tempfile.mkdtemp(prefix="resil_lost_"),
                        faults=FaultInjector().flip_write("alice"))
    eng = _engine(cfg, params, store=store)
    eng.submit(PROMPT_A, lane=0, agent_id="alice")
    eng.submit(PROMPT_B, lane=1, agent_id="bob")
    eng.run(16)
    eng.hibernate("alice")
    eng.wake("alice")
    d0, s0, t0 = (eng.stats["tick_dispatches"], eng.stats["host_syncs"],
                  eng.stats["ticks"])
    eng.run(16)
    eng.flush_wakes()  # make the failing wake terminal before asserting
    # dispatch/sync accounting unchanged by the failing wake: one dispatch
    # and one host sync per sync_every window, exactly
    n_windows = (eng.stats["ticks"] - t0) / eng.sync_every
    assert eng.stats["tick_dispatches"] - d0 == n_windows
    assert eng.stats["host_syncs"] - s0 == n_windows
    assert eng.registry.get("alice").status == LOST
    assert eng.stats["lost_agents"] == 1 and store.stats["quarantined"] == 1
    assert eng.registry.counts()["lost"] == 1
    assert any(e["event"] == "lost" for e in eng.history)
    # bob, untouched: bitwise vs the fault-free reference at the same tick
    bob = next(m for m in eng.mains if m.agent_id == "bob")
    assert eng.stats["ticks"] == 32
    assert bob.tokens == ref_bob.tokens and bob.text == ref_bob.text
    # alice's lane is free again: a new agent can use it immediately
    eng.submit(PROMPT_A, lane=0, agent_id="carol")
    eng.run(4)
    assert eng.mains[0].agent_id == "carol" and eng.mains[0].active
    # waking a LOST agent is a clean error, not a crash
    with pytest.raises(ValueError):
        eng.wake("alice")


def test_transient_wake_failure_stays_hibernated_then_wakes(setup):
    cfg, params = setup
    import tempfile

    store = _cold_store(tempfile.mkdtemp(prefix="resil_transient_"),
                        faults=FaultInjector().fail_read("alice", times=99),
                        wake_retries=2)
    eng = _engine(cfg, params, store=store)
    eng.submit(PROMPT_A, lane=0, agent_id="alice")
    eng.run(8)
    eng.hibernate("alice")
    eng.wake("alice")
    eng.run(8)
    eng.flush_wakes()
    # retries exhausted, but the snapshot is intact: HIBERNATED, not LOST
    assert eng.registry.get("alice").status == HIBERNATED
    assert eng.stats["wake_failures"] == 1 and eng.stats["lost_agents"] == 0
    assert any(e["event"] == "wake_failed" for e in eng.history)
    store.faults = None  # the flaky disk recovers
    view = eng.wake("alice", wait=True)
    assert view.active and eng.registry.get("alice").status == ACTIVE


@pytest.mark.filterwarnings("ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_worker_death_mid_wake_heals_and_engine_continues(setup):
    cfg, params = setup
    import tempfile

    store = _cold_store(tempfile.mkdtemp(prefix="resil_worker_"),
                        faults=FaultInjector().kill_worker_on_read("alice"))
    eng = _engine(cfg, params, store=store)
    eng.submit(PROMPT_A, lane=0, agent_id="alice")
    eng.run(8)
    eng.hibernate("alice")
    eng.wake("alice")
    deadline = time.monotonic() + 30
    while store._worker.is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    eng.run(8)          # boundary ops heal the worker + fail the wake
    eng.flush_wakes()
    assert store.stats["worker_respawns"] == 1
    assert eng.registry.get("alice").status == HIBERNATED  # blob intact
    store.faults = None
    assert eng.wake("alice", wait=True).active


def test_wake_deadline_degrades_blocked_promotion(setup):
    cfg, params = setup
    import tempfile

    release = threading.Event()
    store = _cold_store(
        tempfile.mkdtemp(prefix="resil_deadline_"),
        faults=FaultInjector().block_put("alice", release=release, timeout=30),
    )
    eng = _engine(cfg, params, store=store)
    eng.submit(PROMPT_A, lane=0, agent_id="alice")
    eng.run(8)
    eng.hibernate("alice")
    eng.wake("alice", deadline_s=0.05)
    time.sleep(0.2)
    eng.run(8)   # the overdue ticket expires at the boundary, engine ticks on
    eng.flush_wakes()
    assert eng.registry.get("alice").status == HIBERNATED
    assert eng.stats["wake_failures"] == 1
    release.set()
    store.faults = None
    assert eng.wake("alice", wait=True).active  # second attempt lands


@pytest.mark.filterwarnings("ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_scripted_fault_storm_against_one_engine(setup, tmp_path):
    """Every fault kind in one engine, in one script: a bit-flipped cold
    blob, transient read failures and a killed prefetch worker against
    hibernate/wake churn. Each agent degrades on its own (permanent loss
    -> LOST, transient -> retried or re-woken), the engine keeps ticking,
    and the untouched lane stays bitwise equal to a fault-free engine."""
    cfg, params = setup
    tok = ByteTokenizer(cfg.vocab_size)
    prompts = {"A": "agent A considers the first question at length.",
               "B": "agent B writes a careful second answer here.",
               "C": "agent C is the untouched control stream."}

    def build(store=None):
        eng = CortexEngine(Prism(params, cfg), tok, n_main=3, max_side=2,
                           main_capacity=128, theta=1e9, sync_every=4,
                           sampling=SamplingParams(greedy=True), store=store)
        for lane, (aid, p) in enumerate(prompts.items()):
            eng.submit(p, lane=lane, agent_id=aid)
        return eng

    ref = build()
    ref.run(32)
    ref_c = next(m for m in ref.mains if m.agent_id == "C").text

    faults = (
        FaultInjector()
        .flip_write("A")                          # permanent: A's blob corrupt on disk
        .fail_read("B", nth=1, times=2)           # transient: first wake retries through
        .kill_worker_on_read("B", nth=4)          # second wake kills the worker
    )
    store = _cold_store(tmp_path, faults=faults)
    eng = build(store)
    eng.run(16)
    eng.hibernate("A")
    eng.hibernate("B")
    eng.wake("A")   # corrupt blob -> quarantine -> LOST; engine keeps ticking
    eng.wake("B")   # two injected read failures -> retry -> lands
    eng.run(8)
    eng.flush_wakes()
    assert eng.registry.get("A").status == LOST, eng.registry.get("A").status
    assert eng.registry.get("B").status == ACTIVE, eng.registry.get("B").status
    assert store.stats["quarantined"] == 1 and store.stats["wake_retries"] == 2, store.stats
    # round 2: the prefetch worker dies mid-promotion; supervision must fail
    # the ticket (B stays HIBERNATED, re-wakeable), respawn, then succeed
    eng.hibernate("B")
    eng.wake("B")
    eng.run(4)
    eng.flush_wakes()
    assert eng.registry.get("B").status == HIBERNATED, eng.registry.get("B").status
    assert eng.stats["wake_failures"] >= 1 and store.stats["worker_respawns"] == 1
    eng.wake("B", wait=True)
    eng.run(4)
    eng.flush_wakes()
    assert eng.registry.get("B").status == ACTIVE
    # the control lane never noticed any of it: bitwise parity at tick 32
    assert eng.stats["ticks"] == 32, eng.stats["ticks"]
    chaos_c = next(m for m in eng.mains if m.agent_id == "C").text
    assert chaos_c == ref_c, (chaos_c[:60], ref_c[:60])
    assert eng.stats["lost_agents"] == 1 and eng.stats["wakes"] == 2


def test_fault_injected_wake_overlap_region_zero_transfers(setup):
    """The acceptance bar's zero-transfer invariant UNDER fault injection:
    a wake that retried through transient faults commits between the ring
    fetch and the next dispatch with the overlap region still issuing zero
    device transfers."""
    cfg, params = setup
    import tempfile

    store = _cold_store(tempfile.mkdtemp(prefix="resil_guard_"),
                        faults=FaultInjector().fail_read("alice", times=2))
    eng = _engine(cfg, params, store=store)
    eng.submit(PROMPT_A, lane=0, agent_id="alice")
    eng.run(8)
    eng.hibernate("alice")
    eng.submit(PROMPT_B, lane=0, agent_id="bob")
    eng.drain()
    eng.wake("alice")
    eng._wake_tickets["alice"].result(timeout=60)  # retried, then landed
    assert store.stats["wake_retries"] == 2

    eng._dispatch_window(4)                       # window t
    eng._prefetch_rings()
    rings = eng._fetch_rings()
    assert eng._commit_ready_wakes(mark_fresh=True) == 1
    alice = eng.mains[1]
    assert alice.agent_id == "alice" and alice.active
    with jax.transfer_guard("disallow"):
        assert eng._gate(rings, 4)
        eng._dispatch_window(4)                   # window t+1: alice aboard
        eng._postprocess(rings, 4, overlapped=True)
    eng.drain()
    # and the resumed stream is still the fault-free reference prefix
    ref = _engine(cfg, params)
    ref.submit(PROMPT_A, lane=0, agent_id="alice")
    ref.run(20)
    assert alice.tokens == ref.mains[0].tokens[: len(alice.tokens)]


# ---------------------------------------------------------------------------
# server: per-request wake deadlines + per-request degradation
# ---------------------------------------------------------------------------

def _server(cfg, params, store=None, n_lanes=2):
    return BatchServer(
        params, cfg, ByteTokenizer(cfg.vocab_size), n_lanes=n_lanes,
        capacity=128, sampling=SamplingParams(greedy=True), store=store,
    )


def test_server_unpark_deadline_fails_only_that_request(setup):
    cfg, params = setup
    import tempfile

    release = threading.Event()
    store = SynapseStore(
        warm_capacity_bytes=1,
        cold_dir=tempfile.mkdtemp(prefix="resil_srv_"),
        wake_backoff_s=0.001,
    )
    srv = _server(cfg, params, store=store)
    r1 = srv.submit(PROMPT_A, max_new_tokens=24)
    r2 = srv.submit(PROMPT_B, max_new_tokens=24)
    for _ in range(2):
        srv.tick()
    assert srv.park(r1) and srv.park(r2)
    # the short block timeout lets the single prefetch worker free itself
    # to serve r2 after r1's deadline has already expired host-side
    store.faults = FaultInjector().block_put(f"req{r1}", release=release,
                                            timeout=0.5)
    srv.unpark(r1, deadline_s=0.05)
    srv.unpark(r2)
    done = srv.run_until_done()
    release.set()
    by_rid = {r.rid: r for r in done}
    assert by_rid[r1].error is not None and by_rid[r1].done
    assert by_rid[r2].error is None and by_rid[r2].done
    assert len(by_rid[r2].tokens) == by_rid[r2].prompt_len + 24
    assert srv.stats["lost_requests"] == 1


def test_server_lost_parked_snapshot_degrades_per_request(setup):
    """AgentOS-style per-request degradation: one corrupt parked blob fails
    ONE request (error recorded); the other parked request resumes bitwise
    vs a never-parked reference."""
    cfg, params = setup
    import tempfile

    ref_srv = _server(cfg, params)
    rr = ref_srv.submit(PROMPT_B, max_new_tokens=24)
    ref_done = {r.rid: r for r in ref_srv.run_until_done()}

    store = SynapseStore(
        warm_capacity_bytes=1,
        cold_dir=tempfile.mkdtemp(prefix="resil_srv2_"),
        wake_backoff_s=0.001,
    )
    srv = _server(cfg, params, store=store)
    r1 = srv.submit(PROMPT_A, max_new_tokens=24)
    r2 = srv.submit(PROMPT_B, max_new_tokens=24)
    for _ in range(2):
        srv.tick()
    store.faults = FaultInjector().flip_write(f"req{r1}")
    assert srv.park(r1) and srv.park(r2)
    srv.unpark(r1)
    srv.unpark(r2)
    done = {r.rid: r for r in srv.run_until_done()}
    assert done[r1].error is not None
    assert done[r2].error is None and done[r2].done
    assert store.stats["quarantined"] == 1
    # r2's stream matches the never-parked reference bitwise
    assert done[r2].tokens == ref_done[rr].tokens
