"""Token-level generative serving (ISSUE 11).

Covers: the bucketed KV-cache decode engine (nn/decode.py) — prefill/decode
split correctness against the full forward pass, paged vs contiguous cache
parity, the zero-compile AOT warm contract; the token-level continuous
batching scheduler (serve/scheduler.GenerateWorker) — batched greedy decode
bit-exact vs serving each stream unbatched, streams joining and leaving the
running batch at token boundaries, mid-stream deadline shedding repriced
per remaining token budget, arrival shedding and backpressure; the chunked
HTTP streaming route; the TTFT/ITL/token/occupancy SLO metrics; and the
decode knobs' environment variables.
"""

import json
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import obs, serve
from deeplearning4j_tpu.models import TransformerLM
from deeplearning4j_tpu.nn.decode import DecodeProgram
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.obs import slo
from deeplearning4j_tpu.serve import (
    GenerateConfig,
    ModelRegistry,
    ShedError,
    TokenAdmission,
)
from deeplearning4j_tpu.serve.admission import LatencyModel
from deeplearning4j_tpu.utils import bucketing

VOCAB = 29
MAX_LEN = 64


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("DL4J_TPU_DECODE_BATCH_MAX", "DL4J_TPU_KV_PAGE_TOKENS",
                "DL4J_TPU_KV_PAGED", "DL4J_TPU_PREFILL_CHUNK",
                "DL4J_TPU_GEN_MAX_NEW", "DL4J_TPU_GEN_QUEUE",
                "DL4J_TPU_GEN_DEADLINE_MS", "DL4J_TPU_SERVE_MARGIN_MS",
                "DL4J_TPU_SERVE_MIN_SAMPLES", "DL4J_TPU_SLO_TTFT_MS",
                "DL4J_TPU_SLO_ITL_MS", "DL4J_TPU_AOT",
                "DL4J_TPU_AOT_BUNDLE", "DL4J_TPU_BUCKETING",
                "DL4J_TPU_BUCKETS"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    bucketing.telemetry().reset()
    yield
    obs.reset()
    bucketing.telemetry().reset()


_MODEL_CACHE = {}


def _lm(seed=7):
    # float32 so the cache-path logits can be compared to the full forward
    # (the default bf16 path computes attention in operand dtype)
    if seed not in _MODEL_CACHE:
        _MODEL_CACHE[seed] = MultiLayerNetwork(TransformerLM(
            vocab_size=VOCAB, max_len=MAX_LEN, d_model=32, n_heads=4,
            n_blocks=2, dtype="float32")).init(seed=seed)
    return _MODEL_CACHE[seed]


def _clone(model):
    m = MultiLayerNetwork(model.conf)
    m.init()
    m.params = model.params
    return m


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, size=n).tolist()


def _ref_greedy(model, prompt, n_gen):
    """Oracle: full forward over the growing sequence, argmax at the end."""
    toks = list(prompt)
    out = []
    for _ in range(n_gen):
        x = np.asarray(toks, np.int32)[None, :, None]
        logits = np.asarray(model.output(x), np.float32)
        nxt = int(np.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def _run_program(prog, prompt, n_gen):
    """Drive a DecodeProgram by hand: chunked prefill, then token steps."""
    ladder = prog.ladder
    if prog.paged:
        pages = list(range(1, prog.max_pages + 1))
        npb = ladder.bucket(prog.max_pages)
        table = np.zeros((1, npb), np.int32)
        table[0, :len(pages)] = pages
    else:
        table = np.zeros((1,), np.int32)
    cached, fed, out = 0, 0, []
    while fed < len(prompt):
        chunk = prompt[fed:fed + prog.prefill_chunk]
        tc = ladder.bucket(len(chunk)) if len(chunk) > 1 else 1
        tokens = np.zeros((1, tc), np.int32)
        tokens[0, :len(chunk)] = chunk
        _, ids = prog.dispatch(table, [cached], tokens, [len(chunk)])
        cached += len(chunk)
        fed += len(chunk)
    nxt = int(ids[0])
    out.append(nxt)
    for _ in range(n_gen - 1):
        _, ids = prog.dispatch(table, [cached], [[nxt]], [1])
        cached += 1
        nxt = int(ids[0])
        out.append(nxt)
    return out


# ---------------------------------------------------------------------------
# Decode engine (nn/decode.py)
# ---------------------------------------------------------------------------


class TestDecodeProgram:
    def test_prefill_decode_split_matches_full_forward(self):
        """Chunked prefill + incremental decode == whole-sequence forward:
        the cache path introduces no numeric drift for greedy tokens."""
        model = _lm()
        prog = DecodeProgram(model, page_tokens=8, max_batch=4,
                             prefill_chunk=8, paged=True)
        prompt = _prompt(19, seed=3)  # spans 3 prefill chunks
        assert _run_program(prog, prompt, 6) == _ref_greedy(model, prompt, 6)

    def test_paged_vs_contiguous_parity(self):
        model = _lm()
        paged = DecodeProgram(model, page_tokens=8, max_batch=4,
                              prefill_chunk=16, paged=True)
        contig = DecodeProgram(model, page_tokens=8, max_batch=4,
                               prefill_chunk=16, paged=False)
        for n, seed in ((5, 0), (12, 1), (23, 2)):
            p = _prompt(n, seed=seed)
            assert _run_program(paged, p, 5) == _run_program(contig, p, 5)

    def test_warm_covers_dispatch_grid_zero_compiles_after(self):
        model = _lm()
        prog = DecodeProgram(model, page_tokens=8, max_batch=4,
                             prefill_chunk=16, paged=True)
        n = prog.warm()
        assert n == len(prog.signature_grid())
        tel = bucketing.telemetry()
        c0 = tel.compiles("decode.step")
        _run_program(prog, _prompt(13, seed=5), 4)
        assert tel.compiles("decode.step") == c0, \
            "warmed program compiled on dispatch"

    def test_rejects_models_without_decode_path(self):
        from deeplearning4j_tpu.nn.input_type import InputType
        from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
        from deeplearning4j_tpu.nn.model import MultiLayerConfiguration

        conf = MultiLayerConfiguration(
            layers=(Dense(n_out=8, activation="tanh"),
                    OutputLayer(n_out=2, activation="softmax")),
            input_type=InputType.feed_forward(4),
            updater={"type": "sgd", "lr": 0.1},
        )
        with pytest.raises(ValueError, match="no decode path|TransformerBlock"):
            DecodeProgram(MultiLayerNetwork(conf).init())

    def test_capacity_from_positional_embedding(self):
        prog = DecodeProgram(_lm(), page_tokens=8, max_batch=2,
                             prefill_chunk=8)
        assert prog.capacity == MAX_LEN
        assert prog.max_pages == MAX_LEN // 8


# ---------------------------------------------------------------------------
# Token-level continuous batching (serve/scheduler.GenerateWorker)
# ---------------------------------------------------------------------------


def _cfg(**kw):
    base = dict(decode_batch_max=4, kv_page_tokens=8, prefill_chunk=16,
                max_new_default=8, queue_limit=8, default_deadline_s=30.0)
    base.update(kw)
    return GenerateConfig(**base)


class TestContinuousBatching:
    def test_batched_greedy_bit_exact_vs_unbatched(self):
        """The acceptance gate: concurrent streams through one decode batch
        produce exactly the tokens each would get served alone."""
        model = _lm()
        reg = ModelRegistry()
        try:
            gw = reg.register_generate("lm", model, warm=True, config=_cfg())
            prompts = [_prompt(n, seed=s)
                       for s, n in enumerate((4, 11, 19, 26))]
            streams = [gw.submit(p, max_new=6) for p in prompts]
            batched = [list(s) for s in streams]
            assert all(s.finish_reason == "length" for s in streams)
            assert gw.stats_counters["max_occupancy"] > 1, \
                "streams never actually shared the decode batch"

            solo_worker = reg.register_generate(
                "lm_solo", _clone(model), warm=True, config=_cfg())
            for p, want in zip(prompts, batched):
                assert list(solo_worker.submit(p, max_new=6)) == want
        finally:
            reg.shutdown()

    def test_join_and_leave_at_token_boundaries(self):
        """A stream submitted while another is mid-decode joins the running
        batch (occupancy 2) without perturbing the first stream's tokens,
        and early leave (eos/length) frees its slot for the next join."""
        model = _lm()
        reg = ModelRegistry()
        try:
            gw = reg.register_generate("lm", model, warm=True,
                                       config=_cfg(decode_batch_max=2))
            p1, p2, p3 = (_prompt(6, seed=1), _prompt(9, seed=2),
                          _prompt(5, seed=3))
            s1 = gw.submit(p1, max_new=12)
            it1 = iter(s1)
            first = next(it1)          # s1 is decoding now
            s2 = gw.submit(p2, max_new=3)   # joins mid-flight
            got2 = list(s2)
            assert len(got2) == 3 and s2.finish_reason == "length"
            rest1 = [first] + list(it1)
            assert len(rest1) == 12 and s1.finish_reason == "length"
            # the join/leave around it did not perturb stream 1
            assert rest1 == _ref_greedy(model, p1, 12)
            assert got2 == _ref_greedy(model, p2, 3)
            # both were in the batch together at least once; s2's leave
            # freed the slot s3 then reuses
            assert gw.stats_counters["max_occupancy"] == 2
            s3 = gw.submit(p3, max_new=2)
            assert len(list(s3)) == 2
            assert gw.stats_counters["joins"] == 3
            assert gw.stats_counters["leaves"] == 3
        finally:
            reg.shutdown()

    def test_eos_leaves_early(self):
        model = _lm()
        reg = ModelRegistry()
        try:
            gw = reg.register_generate("lm", model, warm=True, config=_cfg())
            p = _prompt(7, seed=4)
            ref = _ref_greedy(model, p, 8)
            eos = ref[-1]              # guaranteed to occur in the stream
            s = gw.submit(p, max_new=8, eos=eos)
            got = list(s)
            # eos token is emitted, then the stream leaves the batch
            assert got == ref[:ref.index(eos) + 1]
            assert s.finish_reason == "eos"
        finally:
            reg.shutdown()

    def test_midstream_deadline_shed_repriced_per_token(self, monkeypatch):
        """Once the measured ITL says the remaining token budget cannot make
        the deadline, the stream sheds at a token boundary mid-flight."""
        model = _lm()
        reg = ModelRegistry()
        try:
            gw = reg.register_generate(
                "lm", model, warm=True,
                config=_cfg(min_samples=1, margin_s=0.0))
            assert gw.admission.latency is gw.latency
            # the decode step is booked at 1 s whatever this host took for
            # it, and the deadline is far beyond what prefill and a first
            # token can take here: neither a loaded host (deadline gone
            # before the first token) nor a fast one (54 tokens inside the
            # deadline) decides the outcome, the repricing does
            observe = gw.latency.observe
            monkeypatch.setattr(
                gw.latency, "observe", lambda key, bucket, dt: observe(
                    key, bucket, 1.0 if key.endswith(":decode") else dt))
            # ITL is unmeasured at arrival, so admission is optimistic
            # (never shed on a guess); the first decode step activates the
            # estimate (min_samples=1) and repricing the ~54 remaining
            # tokens against it blows the deadline -> shed at a boundary
            s = gw.submit(_prompt(5, seed=1), max_new=55,
                          deadline_s=20.0)
            got = list(s)
            assert s.finish_reason == "shed:deadline"
            assert 0 < len(got) < 55
            assert gw.admission.itl("lm", 1) is not None
            assert gw.stats_counters["shed_midstream"] >= 1
            tracker = slo.slo_tracker()
            assert tracker._shed.value(route="generate.lm",
                                       reason="deadline") >= 1
        finally:
            reg.shutdown()

    def test_arrival_shed_and_backpressure(self):
        model = _lm()
        reg = ModelRegistry()
        try:
            gw = reg.register_generate(
                "lm", model, warm=True, config=_cfg(min_samples=1))
            list(gw.submit(_prompt(4, seed=0), max_new=4))  # measure ITL
            with pytest.raises(ShedError) as ei:
                gw.submit(_prompt(4, seed=1), max_new=40, deadline_s=1e-4)
            assert ei.value.reason == "deadline"
            assert ei.value.http_status == 503
            with pytest.raises(ValueError):
                gw.submit(_prompt(4), max_new=MAX_LEN + 1)
            with pytest.raises(ValueError):
                gw.submit([])
        finally:
            reg.shutdown()

    def test_token_admission_math(self):
        lat = LatencyModel(min_samples=1)
        adm = TokenAdmission(lat, _cfg(min_samples=1, margin_s=0.0))
        # unmeasured: never sheds on a guess
        assert not adm.infeasible("m", 10, 100, deadline=1.0, now=0.0)
        assert not adm.should_shed("m", 100, deadline=1.0, now=0.0)
        for _ in range(3):
            lat.observe("m:decode", 1, 0.01)
            lat.observe("m:prefill", 16, 0.02)
        # 100 tokens x 10ms >> 0.5s deadline
        assert adm.infeasible("m", 10, 100, deadline=0.5, now=0.0)
        assert not adm.infeasible("m", 10, 10, deadline=0.5, now=0.0)
        assert adm.should_shed("m", 100, deadline=0.5, now=0.0)
        assert not adm.should_shed("m", 10, deadline=0.5, now=0.0)
        # past-deadline with zero remaining sheds unconditionally
        assert adm.should_shed("m", 0, deadline=0.5, now=1.0)


# ---------------------------------------------------------------------------
# HTTP streaming + metrics
# ---------------------------------------------------------------------------


class TestGenerateHttp:
    @pytest.fixture()
    def served(self):
        model = _lm()
        reg = ModelRegistry()
        gw = reg.register_generate("lm", model, warm=True, config=_cfg())
        srv = serve.InferenceServer(reg).start(port=0)
        yield srv, gw, model
        srv.stop()

    def _generate(self, port, payload):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/models/lm:generate",
                     json.dumps(payload).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read().decode()
        chunked = resp.getheader("Transfer-Encoding")
        conn.close()
        return resp.status, chunked, body

    def test_streaming_round_trip(self, served):
        srv, gw, model = served
        p = _prompt(6, seed=9)
        status, chunked, body = self._generate(
            srv.port, {"prompt": p, "max_tokens": 5})
        assert status == 200
        assert chunked == "chunked"
        lines = [json.loads(l) for l in body.strip().splitlines()]
        assert [l["token"] for l in lines[:-1]] == _ref_greedy(model, p, 5)
        assert [l["i"] for l in lines[:-1]] == list(range(5))
        tail = lines[-1]
        assert tail["done"] and tail["reason"] == "length"
        assert tail["tokens"] == 5 and tail["ttft_ms"] > 0

    def test_bad_payload_and_unknown_model(self, served):
        srv, _, _ = served
        status, _, body = self._generate(srv.port, {"prompt": []})
        assert status == 400
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        conn.request("POST", "/v1/models/nope:generate",
                     json.dumps({"prompt": [1]}).encode())
        assert conn.getresponse().status == 404
        conn.close()

    def test_slo_metrics_populated(self, served):
        srv, gw, _ = served
        self._generate(srv.port, {"prompt": _prompt(5), "max_tokens": 4})
        tracker = slo.slo_tracker()
        route = "generate.lm"
        assert int(tracker._tokens.value(route=route) or 0) == 4
        ttft = tracker._ttft.summary(route=route)
        itl = tracker._itl.summary(route=route)
        assert ttft and ttft["count"] == 1
        assert itl and itl["count"] == 3
        assert tracker._occupancy.value(model="lm") == 0  # drained
        # the burn-rate machinery saw the stream's tokens
        assert tracker.burn_rate(route) is not None
        text = obs.prometheus_text()
        for fam in ("dl4j_ttft_seconds", "dl4j_itl_seconds",
                    "dl4j_tokens_generated_total",
                    "dl4j_decode_batch_occupancy"):
            assert fam in text

    def test_itl_threshold_burns_budget(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_SLO_ITL_MS", "1000")
        tracker = slo.SloTracker()
        tracker.observe_itl("r", 0.5)
        assert tracker.burn_rate("r") == 0.0
        tracker.observe_itl("r", 2.0)
        assert tracker.burn_rate("r") > 0


# ---------------------------------------------------------------------------
# Registry pipeline + decode knobs
# ---------------------------------------------------------------------------


class TestRegistryAndKnobs:
    def test_register_generate_warm_bundle_cycle(self, tmp_path,
                                                 monkeypatch):
        monkeypatch.setenv("DL4J_TPU_AOT_BUNDLE", "1")
        bundle = str(tmp_path / "lm.aotbundle")
        model = _lm()
        reg = ModelRegistry()
        try:
            gw = reg.register_generate("lm", model, warm=True,
                                       bundle=bundle, config=_cfg())
            meta = [m for m in reg.describe() if m.get("generate")][0]
            assert meta["warmed"] == len(gw.program.signature_grid())
            import os

            assert os.path.exists(bundle)
            # a fresh model restores the decode executables from the bundle
            reg2 = ModelRegistry()
            try:
                gw2 = reg2.register_generate("lm", _clone(model), warm=False,
                                             bundle=bundle, config=_cfg())
                meta2 = [m for m in reg2.describe()
                         if m.get("generate")][0]
                assert meta2["restored"] > 0
            finally:
                reg2.shutdown()
        finally:
            reg.shutdown()

    def test_generate_config_reads_knob_envs(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_KV_PAGE_TOKENS", "32")
        monkeypatch.setenv("DL4J_TPU_DECODE_BATCH_MAX", "16")
        cfg = GenerateConfig.from_env()
        assert cfg.kv_page_tokens == 32
        assert cfg.decode_batch_max == 16
