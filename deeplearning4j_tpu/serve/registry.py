"""Model registry: the import → AOT-warm → serve pipeline, per model pool.

``ModelRegistry`` owns every :class:`~.scheduler.ModelWorker` in the
process. Models enter one of two ways:

- ``register(name, model)`` — an already-constructed model object;
- ``load(name, path)``      — a path, format-detected by
  :func:`deeplearning4j_tpu.modelimport.import_model` (Keras ``.h5`` or
  DL4J ``.zip``).

Either way the model runs the same warm pipeline before it takes traffic:

1. **restore** — if an ``.aotbundle`` sidecar exists (``bundle`` argument,
   or ``<path>.aotbundle`` next to a loaded file) and persistence is
   validated for this backend (``nn/aot.py``), its serialized executables
   are installed so even the first warm call skips XLA entirely;
2. **warm** — ``nn.aot.warm_serving`` AOT-compiles the inference path for
   every ladder bucket reachable by coalesced batches up to the worker's
   ``max_batch``, so the REQUEST PATH NEVER COMPILES (the zero-compile
   gate in tools/serve_smoke.sh);
3. **persist** — the now-warm executables are saved back to the bundle
   path (best-effort, validation-gated) so the next process restores
   instead of recompiling.

All latency measurements share one :class:`~.admission.LatencyModel`
(single ``dl4j_serve_exec_seconds`` family on /metrics), keyed per model.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.serve.admission import (
    GenerateConfig, LatencyModel, ServeConfig)
from deeplearning4j_tpu.serve.scheduler import (
    GenerateWorker, ModelWorker, SearchWorker)

__all__ = ["ModelRegistry"]


class ModelRegistry:
    def __init__(self, config: Optional[ServeConfig] = None):
        self.config = config or ServeConfig.from_env()
        self.latency = LatencyModel(min_samples=self.config.min_samples)
        self._lock = threading.Lock()
        self._workers: Dict[str, ModelWorker] = {}
        self._generators: Dict[str, GenerateWorker] = {}
        self._searchers: Dict[str, SearchWorker] = {}
        self._meta: Dict[str, Dict[str, object]] = {}

    # -- intake ------------------------------------------------------------

    def register(self, name: str, model, warm: bool = True,
                 bundle: Optional[str] = None) -> ModelWorker:
        """Put ``model`` behind a continuous-batching worker under ``name``.
        Replaces (and shuts down) any worker already bound to the name."""
        meta = self._warm_pipeline(name, model, warm=warm, bundle=bundle)
        worker = ModelWorker(name, model, config=self.config,
                             latency=self.latency)
        with self._lock:
            old = self._workers.pop(name, None)
            self._workers[name] = worker
            self._meta[name] = meta
        if old is not None:
            old.shutdown()
        obs.event("serve_model_loaded", model=name, **{
            k: meta[k] for k in ("source", "model_class", "warmed", "restored",
                                 "warm_seconds")})
        return worker

    def register_generate(self, name: str, model, warm: bool = True,
                          bundle: Optional[str] = None,
                          config: Optional[GenerateConfig] = None,
                          capacity: Optional[int] = None) -> GenerateWorker:
        """Put an autoregressive LM behind a token-level continuous-batching
        decode engine under ``name`` (``/v1/generate``).

        Same lifecycle as :meth:`register` but for the DECODE executable
        set: the :class:`~deeplearning4j_tpu.nn.decode.DecodeProgram`'s
        jitted step registers on the model's AOT site table — a ``bundle``
        restore installs its serialized executables BEFORE ``warm`` enumerates the
        (batch x chunk x table) bucket grid, and the now-warm set persists
        back to the bundle, so a cold process streams tokens with zero
        request-path compiles."""
        from deeplearning4j_tpu.nn import aot
        from deeplearning4j_tpu.nn.decode import DecodeProgram

        if getattr(model, "params", None) is None:
            model.init()
        cfg = config or GenerateConfig.from_env()
        program = DecodeProgram(
            model, page_tokens=cfg.kv_page_tokens,
            max_batch=cfg.decode_batch_max, prefill_chunk=cfg.prefill_chunk,
            paged=cfg.paged, capacity=capacity)
        restored = 0
        if bundle:
            restored = aot.restore_bundle(model, bundle)
        warmed = 0
        warm_dt = 0.0
        if warm:
            t0 = time.perf_counter()
            warmed = program.warm()
            warm_dt = time.perf_counter() - t0
            if bundle:
                aot.save_bundle(model, bundle)
        worker = GenerateWorker(name, model, program, config=cfg,
                                latency=self.latency)
        meta = {
            "source": "object",
            "model_class": type(model).__name__,
            "warmed": int(warmed),
            "restored": int(restored),
            "warm_seconds": round(warm_dt, 4),
            "bundle": bundle,
            "generate": True,
        }
        with self._lock:
            old = self._generators.pop(name, None)
            self._generators[name] = worker
            self._meta[f"generate:{name}"] = meta
        if old is not None:
            old.shutdown()
        obs.event("serve_model_loaded", model=name, mode="generate", **{
            k: meta[k] for k in ("source", "model_class", "warmed",
                                 "restored", "warm_seconds")})
        return worker

    def register_index(self, name: str, index, warm: bool = True,
                       bundle: Optional[str] = None) -> SearchWorker:
        """Put a :class:`~deeplearning4j_tpu.search.index.VectorIndex`
        behind a signature-coalescing worker under ``name``
        (``/v1/search``).

        Same lifecycle as :meth:`register`: an ``.aotbundle`` sidecar (if
        given) restores serialized search executables BEFORE the warm pass
        enumerates the (B, k, nprobe) signature grid — on a cold
        bundle-restored process every grid entry is a cache hit and the
        request path never compiles. The tier knobs (``ivf_nlist`` /
        ``ivf_nprobe`` / ``search_batch_max``) act at index BUILD time: by
        registration the index shape is already final."""
        from deeplearning4j_tpu.nn import aot

        restored = 0
        if bundle:
            restored = aot.restore_bundle(index, bundle)
        warmed = 0
        warm_dt = 0.0
        if warm:
            t0 = time.perf_counter()
            warmed = index.warm()
            warm_dt = time.perf_counter() - t0
            if bundle:
                aot.save_bundle(index, bundle)
        worker = SearchWorker(name, index, config=self.config,
                              latency=self.latency)
        meta = {
            "source": "object",
            "model_class": type(index).__name__,
            "warmed": int(warmed),
            "restored": int(restored),
            "warm_seconds": round(warm_dt, 4),
            "bundle": bundle,
            "search": True,
        }
        with self._lock:
            old = self._searchers.pop(name, None)
            self._searchers[name] = worker
            self._meta[f"search:{name}"] = meta
        if old is not None:
            old.shutdown()
        obs.event("serve_model_loaded", model=name, mode="search", **{
            k: meta[k] for k in ("source", "model_class", "warmed",
                                 "restored", "warm_seconds")})
        return worker

    def load(self, name: str, path: str, warm: bool = True,
             bundle: Optional[str] = None) -> ModelWorker:
        """Import the model at ``path`` (format auto-detected) and register
        it. ``bundle`` defaults to the ``<path>.aotbundle`` sidecar."""
        from deeplearning4j_tpu import modelimport
        from deeplearning4j_tpu.nn import aot

        model = modelimport.import_model(path)
        if bundle is None:
            bundle = aot.bundle_path_for(path)
        worker = self.register(name, model, warm=warm, bundle=bundle)
        with self._lock:
            self._meta[name]["source"] = str(path)
        return worker

    def _warm_pipeline(self, name: str, model, warm: bool,
                       bundle: Optional[str]) -> Dict[str, object]:
        from deeplearning4j_tpu.nn import aot

        if getattr(model, "params", None) is None:
            model.init()
        restored = 0
        warmed = 0
        warm_dt = 0.0
        if warm:
            t0 = time.perf_counter()
            restored, warmed = aot.warm_serving_bundled(
                model, self.config.max_batch, bundle)
            warm_dt = time.perf_counter() - t0
        elif bundle:
            restored = aot.restore_bundle(model, bundle)
        return {
            "source": "object",
            "model_class": type(model).__name__,
            "warmed": int(warmed),
            "restored": int(restored),
            "warm_seconds": round(warm_dt, 4),
            "bundle": bundle,
        }

    # -- lookup / introspection -------------------------------------------

    def worker(self, name: str) -> Optional[ModelWorker]:
        with self._lock:
            return self._workers.get(name)

    def generator(self, name: str) -> Optional[GenerateWorker]:
        with self._lock:
            return self._generators.get(name)

    def searcher(self, name: Optional[str] = None) -> Optional[SearchWorker]:
        """Search worker by name; with ``name=None`` (or "default") and
        exactly one index registered, that index — the legacy /knn routes
        carry no index name."""
        with self._lock:
            if name in (None, "default") and name not in self._searchers:
                if len(self._searchers) == 1:
                    return next(iter(self._searchers.values()))
                return None
            return self._searchers.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(set(self._workers) | set(self._generators)
                          | set(self._searchers))

    def describe(self) -> List[Dict[str, object]]:
        """One JSON-friendly row per served model (GET /v1/models)."""
        with self._lock:
            pairs = [(self._workers[n], dict(self._meta.get(n, {})))
                     for n in sorted(self._workers)]
            pairs += [(self._generators[n],
                       dict(self._meta.get(f"generate:{n}", {})))
                      for n in sorted(self._generators)]
            pairs += [(self._searchers[n],
                       dict(self._meta.get(f"search:{n}", {})))
                      for n in sorted(self._searchers)]
        rows = []
        for worker, meta in pairs:
            row = worker.stats()
            row.update(meta)
            rows.append(row)
        return rows

    def shutdown(self):
        with self._lock:
            workers = (list(self._workers.values())
                       + list(self._generators.values())
                       + list(self._searchers.values()))
            self._workers.clear()
            self._generators.clear()
            self._searchers.clear()
            self._meta.clear()
        for w in workers:
            w.shutdown()
