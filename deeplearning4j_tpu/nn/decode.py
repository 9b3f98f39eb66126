"""Bucketed KV-cache decode engine: the autoregressive serving step.

Training and batch inference run whole sequences through ``mln.output``;
autoregressive generation is a different dispatch shape entirely — one new
token (or one prefill chunk) per step against an ever-growing key/value
history. :class:`DecodeProgram` compiles that step ONCE per bucket triple
and keeps the history in a device-resident cache, so steady-state decode
never re-runs the prompt and never compiles:

- **Unified step.** One jitted function serves both phases: prefill is the
  step at chunk width ``Tc`` (a bucket of ``prefill_chunk``), decode is the
  same step at ``Tc = 1``. The step embeds the chunk, walks the transformer
  stack through the layers' ``decode_apply`` paths (single-query attention
  against the cache — ops/flash_attention.decode_attention), scatters the
  chunk's k/v into the cache, and returns next-token logits + greedy ids.

- **Paged cache on the bucket ladder.** The cache is a page pool
  ``[P, page_tokens, H, D]`` per transformer block plus a host-managed page
  table: each stream owns an ordered page list, and a dispatch passes a
  ``[B_bucket, NP_bucket]`` int32 table slice. Every dispatch-visible shape
  — batch rows, chunk width, table width — lives on the shared bucket
  ladder (utils/bucketing.py), so the WHOLE executable set is enumerable
  and AOT-warm at registration (``warm``; the zero-compile serving gate).
  Page 0 is a scratch page: padded batch rows and padded chunk slots direct
  their writes there, so padding never touches a real stream's history.

- **Contiguous mode** (``paged=False``) keeps one ``[S+1, L+1, H, D]``
  strip per slot (row S / column L are the padding scratch) — same step
  math, executables keyed by batch bucket only. It is the parity oracle
  for the paged layout (tests/test_generate.py) and the layout of choice
  when capacity is small enough that paging buys nothing.

- **Bit-exactness.** Greedy decode through this program is bit-exact
  batched vs unbatched: rows are independent, and every padded/masked
  cache position contributes an exact-zero softmax weight (see
  decode_attention) — trailing zero terms that leave real rows' reductions
  unchanged. The serving tier's batched==solo guarantee (PR 8) therefore
  extends to token streams.

The program mutates no model state: ``model.params``/``model.state`` pass
through the jitted step unchanged; only the cache pools (donated) evolve.
"""

from __future__ import annotations

import math
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn import aot
from deeplearning4j_tpu.nn.step_program import layer_scope
from deeplearning4j_tpu.utils import bucketing

__all__ = ["DecodeProgram"]

SITE = "decode.step"


# ---------------------------------------------------------------------------
# Cache views: what a layer's decode_apply sees (paging stays out of layers)
# ---------------------------------------------------------------------------


class _PagedView:
    """One transformer block's window onto the page pool for one dispatch.

    ``pool`` {"k","v"}: [P, page_tokens, H, D]; ``table`` [B, NP] int32
    (page ids per stream, in order — gathered index g along the flattened
    span IS absolute position g); ``positions`` [B, Tc]; ``valid`` [B, Tc]
    marks real chunk slots (padding writes land on scratch page 0)."""

    def __init__(self, pool, table, positions, valid, page_tokens: int):
        self.pool = pool
        self._table = table
        self._pos = positions
        self._valid = valid
        self._pg = page_tokens

    def append(self, k_new, v_new):
        npages = self._table.shape[1]
        slot = jnp.clip(self._pos // self._pg, 0, npages - 1)
        page = jnp.take_along_axis(self._table, slot, axis=1)     # [B, Tc]
        off = self._pos % self._pg
        page = jnp.where(self._valid, page, 0)   # padding -> scratch page
        off = jnp.where(self._valid, off, 0)
        dt = self.pool["k"].dtype
        self.pool = {
            "k": self.pool["k"].at[page, off].set(k_new.astype(dt)),
            "v": self.pool["v"].at[page, off].set(v_new.astype(dt)),
        }

    def gathered(self):
        B, npages = self._table.shape
        shape = (B, npages * self._pg) + self.pool["k"].shape[2:]
        k = jnp.take(self.pool["k"], self._table, axis=0).reshape(shape)
        v = jnp.take(self.pool["v"], self._table, axis=0).reshape(shape)
        return k, v


class _ContiguousView:
    """Contiguous-strip cache window: ``pool`` {"k","v"}: [S+1, L+1, H, D]
    (row S and column L are padding scratch); ``slots`` [B] int32."""

    def __init__(self, pool, slots, positions, valid):
        self.pool = pool
        self._slots = slots
        self._pos = positions
        self._valid = valid

    def append(self, k_new, v_new):
        n_slots, length = self.pool["k"].shape[:2]
        row = jnp.broadcast_to(self._slots[:, None], self._pos.shape)
        row = jnp.where(self._valid, row, n_slots - 1)
        col = jnp.where(self._valid, jnp.clip(self._pos, 0, length - 1),
                        length - 1)
        dt = self.pool["k"].dtype
        self.pool = {
            "k": self.pool["k"].at[row, col].set(k_new.astype(dt)),
            "v": self.pool["v"].at[row, col].set(v_new.astype(dt)),
        }

    def gathered(self):
        return (jnp.take(self.pool["k"], self._slots, axis=0),
                jnp.take(self.pool["v"], self._slots, axis=0))


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------


class DecodeProgram:
    """Compiled decode/prefill step + device cache pools for ONE model.

    Owns: the layer plan (which layers cache, which are positionwise), the
    page pool / contiguous strips, and the AOT-wrapped jitted step
    (site ``decode.step`` on ``model._aot_fns`` — bundle persistence and
    restore ride the existing nn/aot.py machinery). Host-side page
    accounting (free lists, per-stream page lists) belongs to the caller
    (serve/scheduler.GenerateWorker); the program only consumes table
    slices whose SHAPES are already on the ladder.
    """

    def __init__(self, model, *, page_tokens: int = 64, max_batch: int = 8,
                 prefill_chunk: int = 64, paged: bool = True,
                 capacity: Optional[int] = None,
                 ladder: Optional[bucketing.BucketLadder] = None):
        from deeplearning4j_tpu.nn.layers import (
            ActivationLayer, DropoutLayer, EmbeddingSequence, LayerNorm,
            PositionalEmbedding, TransformerBlock)

        if model.params is None:
            model.init()
        self.model = model
        self.ladder = ladder or bucketing.ladder_from_env()
        self.page_tokens = int(page_tokens)
        self.max_batch = int(max_batch)
        self.prefill_chunk = int(prefill_chunk)
        self.paged = bool(paged)
        if self.page_tokens < 1 or self.max_batch < 1 or self.prefill_chunk < 1:
            raise ValueError("page_tokens, max_batch and prefill_chunk must "
                             "be >= 1")

        # layer plan: every layer must be cache-aware or provably
        # positionwise (token t's output depends only on token t) — anything
        # else would silently corrupt incremental decode
        positionwise = (EmbeddingSequence, LayerNorm, DropoutLayer,
                        ActivationLayer)
        plan: List[Tuple[str, object]] = []
        pos_cap = None
        for i, layer in enumerate(model.layers):
            last = i == len(model.layers) - 1
            if isinstance(layer, TransformerBlock):
                plan.append(("block", layer))
            elif isinstance(layer, PositionalEmbedding):
                plan.append(("pos", layer))
                cap = int(layer.max_len)
                pos_cap = cap if pos_cap is None else min(pos_cap, cap)
            elif last and hasattr(layer, "preactivation"):
                plan.append(("out", layer))
            elif isinstance(layer, positionwise):
                plan.append(("through", layer))
            else:
                raise ValueError(
                    f"DecodeProgram: layer {i} ({type(layer).__name__}) has "
                    f"no decode path and is not positionwise — incremental "
                    f"decode would be wrong")
        if plan[-1][0] != "out":
            raise ValueError("DecodeProgram: the final layer must expose "
                             "preactivation() (logits head)")
        self._plan = plan
        self._blocks = [l for kind, l in plan if kind == "block"]
        if not self._blocks:
            raise ValueError("DecodeProgram: model has no TransformerBlock "
                             "to cache")

        self.capacity = int(capacity if capacity is not None
                            else (pos_cap or 512))
        self.max_pages = max(1, math.ceil(self.capacity / self.page_tokens))
        # contiguous strips align to the page grid so both layouts mask the
        # same maximal span
        self.contig_len = self.max_pages * self.page_tokens

        # per-block head geometry from the resolved input types
        self._geom = []
        for i, layer in enumerate(model.layers):
            if isinstance(layer, TransformerBlock):
                C = model.layer_input_types[i].size
                self._geom.append((int(layer.n_heads),
                                   C // int(layer.n_heads)))
        self.pools = self._alloc_pools()
        # the serve executor's step program: donates only the cache pools
        # (params/state are shared across concurrent streams)
        from deeplearning4j_tpu.nn.step_program import StepProgram

        self._fn = StepProgram(self._step, SITE, model=model,
                               donate_argnums=(2,))

    # -- cache allocation ---------------------------------------------------

    def _alloc_pools(self):
        dt = self.model.dtype
        pools = []
        for H, D in self._geom:
            if self.paged:
                P = 1 + self.max_batch * self.max_pages  # +1: scratch page 0
                shape = (P, self.page_tokens, H, D)
            else:
                shape = (self.max_batch + 1, self.contig_len + 1, H, D)
            pools.append({"k": jnp.zeros(shape, dt),
                          "v": jnp.zeros(shape, dt)})
        return tuple(pools)

    def reset(self):
        """Zero the cache pools (stream isolation is by page/slot ownership,
        so this is for tests, not per-request hygiene)."""
        self.pools = self._alloc_pools()

    # -- the jitted step -----------------------------------------------------

    def _step(self, params, state, pools, table, lengths, tokens, n_new):
        """One decode/prefill step. ``table``: [B, NP] page table slice
        (paged) or [B] slot ids (contiguous); ``lengths`` [B]: tokens
        already cached per row; ``tokens`` [B, Tc] int32 chunk (padding 0);
        ``n_new`` [B]: real tokens in each row's chunk. Returns
        ``(pools', logits [B, V] f32 at each row's last real token,
        greedy ids [B] int32)``."""
        B, Tc = tokens.shape
        span = (table.shape[1] * self.page_tokens if self.paged
                else self.contig_len + 1)
        # python body runs once per trace -> counts actual compiles
        bucketing.telemetry().record_trace(SITE, (B, Tc, span))
        positions = lengths[:, None] + jnp.arange(Tc, dtype=jnp.int32)[None]
        valid = jnp.arange(Tc, dtype=jnp.int32)[None] < n_new[:, None]
        a = tokens
        new_pools = list(pools)
        bi = 0
        logits = None
        for li, (kind, layer) in enumerate(self._plan):
            p = params[li]
            with layer_scope(layer, li):
                if kind == "block":
                    if self.paged:
                        view = _PagedView(new_pools[bi], table, positions,
                                          valid, self.page_tokens)
                    else:
                        view = _ContiguousView(new_pools[bi], table, positions,
                                               valid)
                    a = layer.decode_apply(p, a, cache=view,
                                           positions=positions)
                    new_pools[bi] = view.pool
                    bi += 1
                elif kind == "pos":
                    a = layer.decode_apply(p, a, positions)
                elif kind == "out":
                    last = jnp.clip(n_new - 1, 0, Tc - 1).astype(jnp.int32)
                    a_last = jnp.take_along_axis(a, last[:, None, None],
                                                 axis=1)[:, 0]        # [B, C]
                    logits = layer.preactivation(p, a_last).astype(jnp.float32)
                else:  # positionwise passthrough, eval mode
                    a, _ = layer.apply(p, state[li], a, train=False, rng=None,
                                       mask=None)
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tuple(new_pools), logits, ids

    # -- dispatch ------------------------------------------------------------

    def dispatch(self, table, lengths, tokens, n_new):
        """Run one step over the live pools (donated in, replaced out).
        Array args are host arrays shaped to ladder buckets by the caller;
        returns ``(logits, ids)`` still on device."""
        table = jnp.asarray(np.asarray(table, np.int32))
        lengths = jnp.asarray(np.asarray(lengths, np.int32))
        tokens = jnp.asarray(np.asarray(tokens, np.int32))
        n_new = jnp.asarray(np.asarray(n_new, np.int32))
        self.pools, logits, ids = self._fn(
            self.model.params, self.model.state, self.pools, table, lengths,
            tokens, n_new)
        return logits, ids

    # -- AOT warm ------------------------------------------------------------

    def signature_grid(self):
        """The exact (B, Tc, NP) dispatch grid the serving tier can reach:
        decode at Tc=1 over every (batch bucket x table bucket), prefill at
        B=1 over every (chunk bucket x table bucket). NP is None in
        contiguous mode (table width is not a dispatch axis)."""
        b_buckets = aot.reachable_buckets(self.max_batch, self.ladder)
        t_buckets = aot.reachable_buckets(self.prefill_chunk, self.ladder)
        p_buckets = (aot.reachable_buckets(self.max_pages, self.ladder)
                     if self.paged else [None])
        grid = []
        for np_b in p_buckets:
            for b in b_buckets:
                grid.append((b, 1, np_b))
            for tc in t_buckets:
                if tc != 1:
                    grid.append((1, tc, np_b))
        return grid

    def warm(self) -> int:
        """AOT-compile the full reachable decode/prefill executable set so
        the token path never compiles (the serve_smoke.sh zero-compile
        gate). Idempotent; returns the number of executables now warm."""
        t0 = time.perf_counter()
        for b, tc, np_b in self.signature_grid():
            if self.paged:
                table = jnp.zeros((b, np_b), jnp.int32)
            else:
                table = jnp.zeros((b,), jnp.int32)
            self._fn.warm(
                self.model.params, self.model.state, self.pools, table,
                jnp.zeros((b,), jnp.int32), jnp.zeros((b, tc), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                cost_key=f"b{b}t{tc}" + (f"p{np_b}" if np_b else ""))
        obs.event("aot_warmup", site=SITE,
                  executables=self._fn.compiled_count,
                  duration_s=round(time.perf_counter() - t0, 6))
        return self._fn.compiled_count

    @property
    def compiled_count(self) -> int:
        return self._fn.compiled_count
