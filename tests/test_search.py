"""Device-resident ANN search tier (ISSUE 14).

Covers: the exact tier against a numpy brute-force oracle (both metrics),
IVF recall@10 >= 0.9 on a clustered corpus at the default nprobe, IVF+PQ
exact-rerank parity when every cell is probed and the rerank window covers
the corpus, coalesced-vs-individual bit-exactness through the
SearchWorker, incremental add visibility (pending buffer + merge), the
bundle persist -> cold-process restore path with ZERO request-path
compiles, and the /v1/search + legacy /knn HTTP round trip with its
400/404/429/503 semantics.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import obs, serve
from deeplearning4j_tpu.obs import slo
from deeplearning4j_tpu.search import IndexConfig, VectorIndex
from deeplearning4j_tpu.serve.admission import ServeConfig
from deeplearning4j_tpu.serve.scheduler import SearchWorker, ShedError
from deeplearning4j_tpu.utils import bucketing


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("DL4J_TPU_SERVE_MAX_BATCH", "DL4J_TPU_SERVE_QUEUE",
                "DL4J_TPU_SERVE_MARGIN_MS", "DL4J_TPU_SERVE_WAIT_MS",
                "DL4J_TPU_SERVE_WAIT_QUANTUM_MS",
                "DL4J_TPU_SERVE_DEFAULT_DEADLINE_MS",
                "DL4J_TPU_SERVE_MIN_SAMPLES", "DL4J_TPU_SERVE_WORKERS",
                "DL4J_TPU_SLO_LATENCY_MS", "DL4J_TPU_SLO_ROUTE_LATENCY_MS",
                "DL4J_TPU_AOT", "DL4J_TPU_AOT_BUNDLE", "DL4J_TPU_BUCKETING",
                "DL4J_TPU_BUCKETS", "DL4J_TPU_IVF_NLIST",
                "DL4J_TPU_IVF_NPROBE", "DL4J_TPU_SEARCH_BATCH_MAX"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    bucketing.telemetry().reset()
    yield
    obs.reset()
    bucketing.telemetry().reset()


def _clustered(n, dim, n_clusters=16, seed=0, spread=0.05):
    """Gaussian blobs: the corpus shape IVF is built for (and the shape the
    recall gate is honest on — neighbors concentrate in few cells)."""
    rs = np.random.RandomState(seed)
    centers = rs.randn(n_clusters, dim).astype(np.float32)
    pts = centers[rs.randint(0, n_clusters, n)]
    return (pts + spread * rs.randn(n, dim)).astype(np.float32)


def _oracle(corpus, queries, k, metric="euclidean"):
    """Brute-force numpy top-k, smallest distance first."""
    if metric == "cosine":
        c = corpus / np.linalg.norm(corpus, axis=1, keepdims=True)
        q = queries / np.linalg.norm(queries, axis=1, keepdims=True)
        d = 1.0 - q @ c.T
    else:
        d = np.linalg.norm(queries[:, None, :] - corpus[None, :, :], axis=-1)
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return idx, np.take_along_axis(d, idx, axis=1)


def _recall(got_ids, want_ids):
    hits = sum(len(np.intersect1d(g, w)) for g, w in zip(got_ids, want_ids))
    return hits / float(want_ids.size)


# ---------------------------------------------------------------------------
# Kernel correctness: exact tier vs numpy oracle
# ---------------------------------------------------------------------------


class TestExactTier:
    def test_matches_numpy_oracle_euclidean(self):
        rs = np.random.RandomState(1)
        corpus = rs.randn(300, 12).astype(np.float32)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=12, ivf=False, pending_cap=0, max_k=8, batch_max=8))
        q = rs.randn(7, 12).astype(np.float32)
        ids, dist = ix.search(q, k=5, tier="exact")
        oid, od = _oracle(corpus, q, 5)
        assert _recall(ids, oid) == 1.0
        np.testing.assert_allclose(dist, od, rtol=1e-4, atol=1e-4)

    def test_matches_numpy_oracle_cosine(self):
        rs = np.random.RandomState(2)
        corpus = rs.randn(200, 10).astype(np.float32)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=10, metric="cosine", ivf=False, pending_cap=0, max_k=4,
            batch_max=4))
        q = rs.randn(5, 10).astype(np.float32)
        ids, dist = ix.search(q, k=4, tier="exact")
        oid, od = _oracle(corpus, q, 4, metric="cosine")
        assert _recall(ids, oid) == 1.0
        np.testing.assert_allclose(dist, od, rtol=1e-4, atol=1e-4)

    def test_self_query_is_own_nearest_neighbor(self):
        corpus = _clustered(400, 8, seed=3)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=8, ivf=False, pending_cap=0, max_k=4, batch_max=4))
        ids, dist = ix.search(corpus[:4], k=1, tier="exact")
        assert list(ids[:, 0]) == [0, 1, 2, 3]
        np.testing.assert_allclose(dist[:, 0], 0.0, atol=1e-4)

    def test_validation_errors(self):
        corpus = np.eye(6, dtype=np.float32)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=6, ivf=False, pending_cap=0, max_k=4, batch_max=4))
        with pytest.raises(ValueError):
            ix.search(np.zeros((1, 5), np.float32), k=2)     # wrong dim
        with pytest.raises(ValueError):
            ix.search(np.zeros((1, 6), np.float32), k=99)    # k > max_k
        with pytest.raises(ValueError):
            ix.search(np.zeros((1, 6), np.float32), k=2, tier="ivf")


# ---------------------------------------------------------------------------
# ANN tiers: IVF recall, PQ rerank parity
# ---------------------------------------------------------------------------


class TestAnnTiers:
    def test_ivf_recall_at_10(self):
        corpus = _clustered(2000, 16, n_clusters=24, seed=4)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=16, max_k=16, batch_max=8, train_sample=2000))
        assert "ivf" in ix.available_tiers()
        q = _clustered(32, 16, n_clusters=24, seed=5)
        ids, _ = ix.search(q, k=10, tier="ivf")
        oid, _ = _oracle(corpus, q, 10)
        assert _recall(ids, oid) >= 0.9
        # the build-time probe published the same figure as a gauge
        assert ix.stats["recall_at_10_ivf"] >= 0.9
        g = obs.snapshot()["metrics"].get("dl4j_search_recall_at_k", {})
        assert any(v >= 0.9 for v in g.values()), g

    def test_ivf_full_probe_equals_exact(self):
        """nprobe = nlist scans every cell: IVF must reproduce the exact
        tier's answer (the posting lists partition the corpus)."""
        corpus = _clustered(600, 12, seed=6)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=12, nlist=8, max_k=8, batch_max=4, train_sample=600))
        q = corpus[100:104] + 0.01
        e_ids, e_d = ix.search(q, k=8, tier="exact")
        i_ids, i_d = ix.search(q, k=8, tier="ivf", nprobe=8)
        assert _recall(i_ids, e_ids) == 1.0
        np.testing.assert_allclose(np.sort(i_d), np.sort(e_d),
                                   rtol=1e-4, atol=1e-4)

    def test_pq_rerank_parity_with_exact(self):
        """With every cell probed and a rerank window covering the whole
        corpus, the ADC pass only orders candidates — the float32 rerank
        decides, so IVF+PQ == exact."""
        corpus = _clustered(512, 16, seed=7)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=16, nlist=4, pq_m=4, pq_ksub=16, rerank=512, max_k=8,
            batch_max=4, train_sample=512))
        assert ix.default_tier == "ivf_pq"
        q = _clustered(8, 16, seed=8)
        e_ids, e_d = ix.search(q, k=8, tier="exact")
        p_ids, p_d = ix.search(q, k=8, tier="ivf_pq", nprobe=4)
        assert _recall(p_ids, e_ids) == 1.0
        np.testing.assert_allclose(np.sort(p_d), np.sort(e_d),
                                   rtol=1e-4, atol=1e-4)

    def test_candidates_scanned_histogram(self):
        corpus = _clustered(1000, 8, seed=9)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=8, nlist=8, nprobe=2, max_k=4, batch_max=4,
            train_sample=1000))
        obs.reset()
        ix.search(corpus[:2], k=4, tier="ivf")
        ix.search(corpus[:2], k=4, tier="exact")
        m = obs.snapshot()["metrics"]["dl4j_search_candidates_scanned"]
        ivf = next(v for lk, v in m.items() if lk.endswith("tier=ivf"))
        exact = next(v for lk, v in m.items() if lk.endswith("tier=exact"))
        # IVF probes 2 of 8 cells; exact scans the full corpus
        assert exact["max"] == 1000.0
        assert 0 < ivf["max"] < 1000.0

    def test_request_counter_by_tier(self):
        corpus = _clustered(300, 8, seed=10)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=8, nlist=4, max_k=4, batch_max=4, train_sample=300))
        obs.reset()
        ix.search(corpus[:1], k=2, tier="exact")
        ix.search(corpus[:1], k=2, tier="ivf")
        ix.search(corpus[:1], k=2, tier="ivf")
        m = obs.snapshot()["metrics"]["dl4j_search_requests_total"]
        assert m["index=default|tier=exact"] == 1
        assert m["index=default|tier=ivf"] == 2


# ---------------------------------------------------------------------------
# Coalescing bit-exactness (worker) and incremental add
# ---------------------------------------------------------------------------


class TestWorkerAndMutation:
    def test_coalesced_matches_individual_bit_exact(self, monkeypatch):
        """One-row submits and a coalesced 4-row batch pad to the SAME
        bucket -> same executable -> bitwise-identical results."""
        monkeypatch.setenv("DL4J_TPU_BUCKETS", "4,8")
        corpus = _clustered(500, 12, seed=11)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=12, nlist=8, max_k=4, batch_max=4, train_sample=500))
        q = _clustered(4, 12, seed=12)
        solo = [ix.search(q[i:i + 1], k=4) for i in range(4)]
        batch_ids, batch_d = ix.search(q, k=4)
        for i, (ids, dist) in enumerate(solo):
            assert np.array_equal(ids[0], batch_ids[i])
            assert np.array_equal(dist[0], batch_d[i])

        w = SearchWorker("coal", ix,
                         config=ServeConfig(max_batch=4, queue_limit=32))
        try:
            results = [None] * 4
            barrier = threading.Barrier(4)

            def one(i):
                barrier.wait()
                results[i] = w.submit(q[i:i + 1], k=4, deadline_s=30.0)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, (ids, dist, tier) in enumerate(results):
                assert np.array_equal(ids[0], batch_ids[i])
                assert np.array_equal(dist[0], batch_d[i])
        finally:
            w.shutdown()

    def test_incremental_add_visible_before_and_after_merge(self):
        corpus = _clustered(400, 10, seed=13)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=10, nlist=8, max_k=4, batch_max=4, train_sample=400,
            pending_cap=16))
        far = np.full((1, 10), 25.0, np.float32)
        (new_id,) = ix.add(far)
        assert new_id == 400 and ix._pending_n == 1
        # visible to every tier immediately (pending rows ride an exact
        # side-scan merged on device)
        for tier in ix.available_tiers():
            ids, dist = ix.search(far, k=1, tier=tier)
            assert ids[0, 0] == new_id, tier
            assert dist[0, 0] < 1e-3
        moved = ix.merge_pending()
        assert moved == 1 and ix._pending_n == 0 and ix.n == 401
        for tier in ix.available_tiers():
            ids, _ = ix.search(far, k=1, tier=tier)
            assert ids[0, 0] == new_id, tier

    def test_add_overflow_forces_merge(self):
        corpus = _clustered(200, 8, seed=14)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=8, ivf=False, max_k=4, batch_max=4, pending_cap=4))
        rs = np.random.RandomState(15)
        new = (rs.randn(11, 8) * 0.1 + 30.0).astype(np.float32)
        ids = ix.add(new)
        assert list(ids) == list(range(200, 211))
        assert ix.n + ix._pending_n == 211
        assert ix._pending_n < 11          # the buffer forced merges
        got, _ = ix.search(new[5:6], k=1)  # id survives the merges
        assert got[0, 0] == 205

    def test_add_disabled_without_pending_buffer(self):
        ix = VectorIndex.build(np.eye(4, dtype=np.float32), IndexConfig(
            dim=4, ivf=False, max_k=2, batch_max=2, pending_cap=0))
        with pytest.raises(ValueError):
            ix.add(np.ones((1, 4), np.float32))


# ---------------------------------------------------------------------------
# Persistence: bundle restore on a COLD process, zero request-path compiles
# ---------------------------------------------------------------------------


class TestPersistence:
    def test_save_load_roundtrip_same_process(self, tmp_path):
        corpus = _clustered(600, 12, seed=16)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=12, nlist=8, pq_m=4, pq_ksub=16, max_k=4, batch_max=4,
            train_sample=600, pending_cap=8))
        ix.add(_clustered(3, 12, seed=17))           # save() must merge
        p = str(tmp_path / "ix.zip")
        ix.save(p)
        ix2 = VectorIndex.load(p)
        assert ix2.n == 603 and ix2._pending_n == 0
        assert ix2.available_tiers() == ix.available_tiers()
        q = corpus[:5]
        for tier in ix.available_tiers():
            a_ids, a_d = ix.search(q, k=4, tier=tier)
            b_ids, b_d = ix2.search(q, k=4, tier=tier)
            assert np.array_equal(a_ids, b_ids), tier
            np.testing.assert_allclose(a_d, b_d, rtol=1e-5)

    def test_corrupt_index_file_rejected(self, tmp_path):
        corpus = np.eye(8, dtype=np.float32)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=8, ivf=False, max_k=2, batch_max=2, pending_cap=0))
        p = str(tmp_path / "ix.zip")
        ix.save(p)
        raw = bytearray(open(p, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(p, "wb").write(bytes(raw))
        with pytest.raises(Exception):
            VectorIndex.load(p)

    def test_cold_restore_zero_request_path_compiles(self, tmp_path):
        """The acceptance gate, end to end in a REAL cold process: phase 1
        builds + warms + persists index and bundle; phase 2 (fresh
        interpreter, compile cache empty) loads, restores, warms and serves
        a burst — asserting bit-exact answers vs phase 1, ZERO traces on any
        search site across the serving window, and that warm compiled only
        what the bundle could not hold (XLA:CPU refuses to serialize the
        two executables whose top-k lowers to a ``LessThan`` sort;
        ``save_bundle`` skips those and ships the rest)."""
        script = textwrap.dedent("""
            import json, os, sys
            import numpy as np
            os.environ["DL4J_TPU_AOT_BUNDLE"] = "1"
            from deeplearning4j_tpu.nn import aot
            from deeplearning4j_tpu.search import IndexConfig, VectorIndex

            d = sys.argv[2]
            ipath = os.path.join(d, "ix.zip")
            bpath = os.path.join(d, "ix.aotbundle")
            rs = np.random.RandomState(18)
            centers = rs.randn(8, 12).astype(np.float32)
            pts = (centers[rs.randint(0, 8, 600)]
                   + 0.05 * rs.randn(600, 12)).astype(np.float32)
            q = rs.randn(6, 12).astype(np.float32)
            phase = sys.argv[1]
            if phase == "build":
                ix = VectorIndex.build(pts, IndexConfig(
                    dim=12, nlist=8, pq_m=4, pq_ksub=16, max_k=4,
                    batch_max=4, train_sample=600, pending_cap=0))
                warmed = ix.warm()
                saved = aot.save_bundle(ix, bpath)
                ix.save(ipath)
                ids, dist = ix.search(q, k=4)
                np.savez(os.path.join(d, "ref.npz"), ids=ids, dist=dist)
                print(json.dumps({"warmed": int(warmed),
                                  "saved": int(saved["entries"])}))
            else:
                ix = VectorIndex.load(ipath)
                restored = aot.restore_bundle(ix, bpath)
                ix.warm()
                warm_compiles = ix.program.compiles_observed()
                ids, dist = ix.search(q, k=4)
                ids2, dist2 = ix.search(q[:1], k=4, tier="exact")
                compiles = ix.program.compiles_observed() - warm_compiles
                ref = np.load(os.path.join(d, "ref.npz"))
                assert np.array_equal(ids, ref["ids"])
                assert np.array_equal(dist, ref["dist"])
                print(json.dumps({"restored": int(restored),
                                  "warm_compiles": int(warm_compiles),
                                  "request_path_compiles": int(compiles)}))
        """)
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        said = {}
        for phase in ("build", "serve"):
            proc = subprocess.run(
                [sys.executable, "-c", script, phase, str(tmp_path)],
                env=env, capture_output=True, text=True, timeout=600)
            assert proc.returncode == 0, proc.stdout + proc.stderr
            said[phase] = json.loads(proc.stdout.strip().splitlines()[-1])
        built, out = said["build"], said["serve"]
        assert out["restored"] == built["saved"] > 0
        assert out["warm_compiles"] == built["warmed"] - built["saved"]
        assert out["request_path_compiles"] == 0


# ---------------------------------------------------------------------------
# HTTP round trip
# ---------------------------------------------------------------------------


def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return r.status, json.loads(r.read())


class TestHttp:
    @pytest.fixture()
    def server(self):
        corpus = _clustered(500, 8, seed=19)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=8, nlist=8, max_k=8, batch_max=8, train_sample=500,
            pending_cap=8))
        reg = serve.ModelRegistry()
        reg.register_index("vecs", ix, warm=False)
        srv = serve.InferenceServer(reg).start(port=0)
        srv.corpus = corpus
        try:
            yield srv
        finally:
            srv.stop()

    def test_v1_search_roundtrip(self, server):
        q = server.corpus[3:5].tolist()
        status, body = _post(server.port, "/v1/search",
                             {"index": "vecs", "queries": q, "k": 3})
        assert status == 200
        assert body["rows"] == 2 and body["tier"] in ("ivf", "exact")
        assert body["ids"][0][0] == 3 and body["ids"][1][0] == 4
        assert len(body["ids"][0]) == 3 and len(body["distances"][0]) == 3

    def test_legacy_knn_routes(self, server):
        status, body = _post(server.port, "/knn", {"ndarray": 7, "k": 4})
        assert status == 200
        got = [r["index"] for r in body["results"]]
        assert len(got) == 4 and 7 not in got
        status, body = _post(server.port, "/knnnew",
                             {"ndarray": server.corpus[9].tolist(), "k": 2})
        assert status == 200
        assert body["results"][0]["index"] == 9
        assert body["results"][0]["distance"] < 1e-3
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/status") as r:
            s = json.loads(r.read())
        assert s == {"ok": True, "points": 500, "dim": 8}

    def test_bad_requests_400(self, server):
        for payload in (
                {"index": "vecs", "queries": [[1.0] * 5], "k": 2},  # dim
                {"index": "vecs", "queries": [[1.0] * 8], "k": 99},  # k
                {"index": "vecs", "queries": [[1.0] * 8], "k": 2,
                 "tier": "bogus"},
                {"index": "vecs", "queries": "nope", "k": 2}):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(server.port, "/v1/search", payload)
            assert ei.value.code == 400, payload
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server.port, "/knn", {"ndarray": 10_000, "k": 2})
        assert ei.value.code == 400

    def test_unknown_index_404(self, server):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server.port, "/v1/search",
                  {"index": "nope", "queries": [[0.0] * 8], "k": 1})
        assert ei.value.code == 404

    def test_infeasible_deadline_503(self, server):
        w = server.registry.searcher("vecs")
        lkey = "vecs:" + w.index.default_tier
        b = w.admission._bucket(1)
        for _ in range(3):
            w.latency.observe(lkey, b, 10.0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server.port, "/v1/search",
                  {"index": "vecs",
                   "queries": [server.corpus[0].tolist()],
                   "k": 2, "deadline_ms": 5})
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["shed"] == "deadline"

    def test_backpressure_429(self):
        corpus = _clustered(200, 8, seed=20)
        ix = VectorIndex.build(corpus, IndexConfig(
            dim=8, ivf=False, max_k=4, batch_max=4, pending_cap=0))
        real = ix.search

        def slow(*a, **kw):
            import time
            time.sleep(0.05)
            return real(*a, **kw)

        ix.search = slow
        reg = serve.ModelRegistry(
            config=ServeConfig(max_batch=4, queue_limit=1, workers=1))
        reg.register_index("vecs", ix, warm=False)
        srv = serve.InferenceServer(reg).start(port=0)
        try:
            codes, retry_after = [], []

            def blast():
                try:
                    status, _ = _post(srv.port, "/v1/search",
                                      {"index": "vecs",
                                       "queries": corpus[:4].tolist(),
                                       "k": 2, "deadline_ms": 30000})
                    codes.append(status)
                except urllib.error.HTTPError as e:
                    codes.append(e.code)
                    if e.code == 429:
                        retry_after.append(e.headers.get("Retry-After"))

            threads = [threading.Thread(target=blast) for _ in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert 429 in codes and 200 in codes
            assert retry_after and retry_after[0] is not None
            tracker = slo.slo_tracker()
            assert tracker._shed.value(route="search:http",
                                       reason="backpressure") is not None
        finally:
            srv.stop()

    def test_per_route_slo_threshold(self, monkeypatch, server):
        monkeypatch.setenv("DL4J_TPU_SLO_ROUTE_LATENCY_MS",
                           "search:http=50,generate=2000")
        slo._reset_tracker()
        t = slo.slo_tracker()
        assert t.threshold_for("search:http") == pytest.approx(0.05)
        assert t.threshold_for("generate:http") == pytest.approx(2.0)
        assert t.threshold_for("serve.toy:http") == pytest.approx(0.25)
        # a 60ms search burns budget under its 50ms envelope while the
        # same latency on a predict route would have been healthy
        t.observe("search:http", 0.06)
        assert t.burn_rate("search:http") > 0
        t.observe("serve.toy:http", 0.06)
        assert t.burn_rate("serve.toy:http") == 0
