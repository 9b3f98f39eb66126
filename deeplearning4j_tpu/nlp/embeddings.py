"""SequenceVectors / Word2Vec / ParagraphVectors — TPU-native embedding training.

Capability parity with the reference's embedding stack (SURVEY.md §2.7):
models/sequencevectors/SequenceVectors.java:49 (fit:192, trainSequence:342),
learning/impl/elements/{SkipGram,CBOW}.java, models/word2vec/Word2Vec.java,
models/paragraphvectors/ParagraphVectors.java,
models/embeddings/inmemory/InMemoryLookupTable.java.

TPU-first redesign: the reference trains with per-pair axpy ops on JVM
threads (AsyncSequencer producer + VectorCalculationsThread consumers,
SequenceVectors.java:1021,1127). Here training pairs are generated host-side
into BATCHED index arrays and each batch is ONE jitted step: gathers of the
embedding rows, a dot-product logistic loss (negative sampling) or Huffman
hierarchical softmax, and scatter-adds back — all fused by XLA, with the
embedding matmuls on the MXU. Same objective, same hyperparameters
(window, negative, subsampling, lr decay), orders of magnitude fewer
dispatches.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.vocab import (
    VocabCache,
    VocabConstructor,
    build_huffman,
    huffman_tables,
    subsample_probs,
    unigram_table,
)

# ---------------------------------------------------------------------------
# jitted steps
# ---------------------------------------------------------------------------


def _sg_ns_step(params, centers, contexts, negs, lr):
    """Skip-gram negative sampling: one batch, full fused update.

    centers/contexts: [B] int32; negs: [B,K] int32.
    loss = -log σ(c·t) - Σ log σ(-c·n).
    """
    syn0, syn1 = params["syn0"], params["syn1neg"]
    c = syn0[centers]                       # [B,D]
    t = syn1[contexts]                      # [B,D]
    n = syn1[negs]                          # [B,K,D]

    pos_dot = jnp.sum(c * t, axis=-1)                     # [B]
    neg_dot = jnp.einsum("bd,bkd->bk", c, n)              # [B,K]
    loss = -jnp.mean(
        jax.nn.log_sigmoid(pos_dot) + jnp.sum(jax.nn.log_sigmoid(-neg_dot), axis=-1)
    )

    # manual gradients (cheaper than autodiff's full-vocab zeros):
    gpos = jax.nn.sigmoid(pos_dot) - 1.0                  # [B]
    gneg = jax.nn.sigmoid(neg_dot)                        # [B,K]
    d_c = gpos[:, None] * t + jnp.einsum("bk,bkd->bd", gneg, n)
    d_t = gpos[:, None] * c
    d_n = gneg[..., None] * c[:, None, :]

    syn0 = syn0.at[centers].add(-lr * d_c)
    syn1 = syn1.at[contexts].add(-lr * d_t)
    syn1 = syn1.at[negs.reshape(-1)].add(-lr * d_n.reshape(-1, d_n.shape[-1]))
    return {"syn0": syn0, "syn1neg": syn1, **{k: v for k, v in params.items()
                                              if k not in ("syn0", "syn1neg")}}, loss


def _sg_ns_epoch_scan(params, centers2d, contexts2d, cum_table, key,
                      lr0, min_lr, seen0, total, negative: int,
                      unroll: int = 4):
    """lax.scan of _sg_ns_step over [N, B] pair chunks, negatives drawn
    ON-DEVICE by inverse-CDF over the unigram table. One dispatch (and ONE
    host->device transfer of the pair arrays) covers N batches — this
    removes the per-batch dispatch round trip that otherwise dominates
    end-to-end corpus training."""
    N, B = centers2d.shape

    def body(carry, xs):
        prm, k, seen = carry
        c, t = xs
        k, sub = jax.random.split(k)
        u = jax.random.uniform(sub, (B, negative))
        negs = jnp.clip(jnp.searchsorted(cum_table, u),
                        0, cum_table.shape[0] - 1).astype(jnp.int32)
        frac = jnp.minimum(seen / total, 1.0)
        lr = jnp.maximum(lr0 * (1.0 - frac), min_lr)
        prm, loss = _sg_ns_step(prm, c, t, negs, lr)
        return (prm, k, seen + B), loss

    # unroll=4 default: scan-of-scatter on TPU runs ~4x faster partially
    # unrolled (measured 283 -> 64 ms/step at B=64K, V=100K; unroll=16 is
    # no better and triples compile time). unroll=1 ~halves the first-epoch
    # compile at ~4x warm-epoch cost
    # -- or keep 4 and amortize compiles across processes with
    # utils/compile_cache.enable_compilation_cache.
    (params, _, _), losses = jax.lax.scan(
        body, (params, key, jnp.asarray(seen0, jnp.float32)),
        (centers2d, contexts2d), unroll=unroll)
    return params, losses


def _cbow_ns_step(params, context_win, win_mask, targets, negs, lr):
    """CBOW negative sampling: mean of window vectors predicts the target.

    context_win: [B,W] int32 (padded), win_mask: [B,W], targets: [B],
    negs: [B,K].
    """
    syn0, syn1 = params["syn0"], params["syn1neg"]
    ctx = syn0[context_win]                                # [B,W,D]
    cnt = jnp.maximum(jnp.sum(win_mask, axis=-1, keepdims=True), 1.0)
    h = jnp.sum(ctx * win_mask[..., None], axis=1) / cnt   # [B,D]
    t = syn1[targets]
    n = syn1[negs]
    pos_dot = jnp.sum(h * t, axis=-1)
    neg_dot = jnp.einsum("bd,bkd->bk", h, n)
    loss = -jnp.mean(
        jax.nn.log_sigmoid(pos_dot) + jnp.sum(jax.nn.log_sigmoid(-neg_dot), axis=-1)
    )
    gpos = jax.nn.sigmoid(pos_dot) - 1.0
    gneg = jax.nn.sigmoid(neg_dot)
    d_h = gpos[:, None] * t + jnp.einsum("bk,bkd->bd", gneg, n)   # [B,D]
    d_t = gpos[:, None] * h
    d_n = gneg[..., None] * h[:, None, :]
    d_ctx = (d_h / cnt)[:, None, :] * win_mask[..., None]          # [B,W,D]

    syn0 = syn0.at[context_win.reshape(-1)].add(-lr * d_ctx.reshape(-1, d_ctx.shape[-1]))
    syn1 = syn1.at[targets].add(-lr * d_t)
    syn1 = syn1.at[negs.reshape(-1)].add(-lr * d_n.reshape(-1, d_n.shape[-1]))
    return {"syn0": syn0, "syn1neg": syn1, **{k: v for k, v in params.items()
                                              if k not in ("syn0", "syn1neg")}}, loss


def _dm_ns_step(params, doc_ids, context_win, win_mask, targets, negs, lr):
    """PV-DM negative sampling (models/embeddings/learning/impl/sequence/
    DM.java): the document vector and the window-word average JOINTLY (mean
    over doc + context vectors) predict the center word.

    doc_ids: [B] int32 (label rows of syn0); context_win: [B,W] padded,
    win_mask: [B,W]; targets: [B]; negs: [B,K].
    """
    syn0, syn1 = params["syn0"], params["syn1neg"]
    ctx = syn0[context_win]                                # [B,W,D]
    doc = syn0[doc_ids]                                    # [B,D]
    cnt = jnp.sum(win_mask, axis=-1, keepdims=True) + 1.0  # + the doc vector
    h = (jnp.sum(ctx * win_mask[..., None], axis=1) + doc) / cnt
    t = syn1[targets]
    n = syn1[negs]
    pos_dot = jnp.sum(h * t, axis=-1)
    neg_dot = jnp.einsum("bd,bkd->bk", h, n)
    loss = -jnp.mean(
        jax.nn.log_sigmoid(pos_dot) + jnp.sum(jax.nn.log_sigmoid(-neg_dot), axis=-1)
    )
    gpos = jax.nn.sigmoid(pos_dot) - 1.0
    gneg = jax.nn.sigmoid(neg_dot)
    d_h = gpos[:, None] * t + jnp.einsum("bk,bkd->bd", gneg, n)   # [B,D]
    d_t = gpos[:, None] * h
    d_n = gneg[..., None] * h[:, None, :]
    d_shared = d_h / cnt
    d_ctx = d_shared[:, None, :] * win_mask[..., None]             # [B,W,D]

    syn0 = syn0.at[context_win.reshape(-1)].add(-lr * d_ctx.reshape(-1, d_ctx.shape[-1]))
    syn0 = syn0.at[doc_ids].add(-lr * d_shared)
    syn1 = syn1.at[targets].add(-lr * d_t)
    syn1 = syn1.at[negs.reshape(-1)].add(-lr * d_n.reshape(-1, d_n.shape[-1]))
    return {"syn0": syn0, "syn1neg": syn1, **{k: v for k, v in params.items()
                                              if k not in ("syn0", "syn1neg")}}, loss


def _cbow_hs_step(params, context_win, win_mask, codes, points, hmask, lr):
    """CBOW hierarchical softmax (CBOW.java HS branch): the window MEAN
    walks the target word's Huffman path.

    context_win/win_mask: [B,W] padded window; codes/points/hmask: [B,L]
    Huffman path of the TARGET word (bit, inner-node idx, validity).
    """
    syn0, syn1 = params["syn0"], params["syn1"]
    ctx = syn0[context_win]                                # [B,W,D]
    cnt = jnp.maximum(jnp.sum(win_mask, axis=-1, keepdims=True), 1.0)
    h = jnp.sum(ctx * win_mask[..., None], axis=1) / cnt   # [B,D]
    w = syn1[points]                                       # [B,L,D]
    dot = jnp.einsum("bd,bld->bl", h, w)
    sign = 1.0 - 2.0 * codes
    loss = -jnp.sum(jax.nn.log_sigmoid(sign * dot) * hmask) / jnp.maximum(
        jnp.sum(hmask), 1.0)
    # dL/ddot of -log sigmoid((1-2c)*dot) is sigmoid(dot) - (1-c): the
    # word2vec label is 1-code (word2vec.c: g = (1 - code - f))
    g = (jax.nn.sigmoid(dot) - (1.0 - codes)) * hmask      # [B,L]
    d_h = jnp.einsum("bl,bld->bd", g, w)
    d_w = g[..., None] * h[:, None, :]
    d_ctx = (d_h / cnt)[:, None, :] * win_mask[..., None]  # [B,W,D]
    syn0 = syn0.at[context_win.reshape(-1)].add(-lr * d_ctx.reshape(-1, d_ctx.shape[-1]))
    syn1 = syn1.at[points.reshape(-1)].add(-lr * d_w.reshape(-1, d_w.shape[-1]))
    return {"syn0": syn0, "syn1": syn1, **{k: v for k, v in params.items()
                                           if k not in ("syn0", "syn1")}}, loss


def _sg_hs_step(params, centers, codes, points, mask, lr):
    """Skip-gram hierarchical softmax over Huffman paths.

    centers [B]; codes/points/mask [B,L] (bit, inner-node idx, validity).
    loss = -Σ log σ((1-2*code) * c·syn1[point]).
    """
    syn0, syn1 = params["syn0"], params["syn1"]
    c = syn0[centers]                                    # [B,D]
    w = syn1[points]                                     # [B,L,D]
    dot = jnp.einsum("bd,bld->bl", c, w)
    sign = 1.0 - 2.0 * codes
    loss = -jnp.sum(jax.nn.log_sigmoid(sign * dot) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    # word2vec label is 1-code (word2vec.c: g = (1 - code - f)); the prior
    # g = sigmoid-code trained the mirrored convention: embeddings came out
    # isomorphic but the reported loss INCREASED while training
    g = (jax.nn.sigmoid(dot) - (1.0 - codes)) * mask     # [B,L] (w2v's -g)
    d_c = jnp.einsum("bl,bld->bd", g, w)
    d_w = g[..., None] * c[:, None, :]
    syn0 = syn0.at[centers].add(-lr * d_c)
    syn1 = syn1.at[points.reshape(-1)].add(-lr * d_w.reshape(-1, d_w.shape[-1]))
    return {"syn0": syn0, "syn1": syn1, **{k: v for k, v in params.items()
                                           if k not in ("syn0", "syn1")}}, loss


# ---------------------------------------------------------------------------
# host-side pair generation
# ---------------------------------------------------------------------------


class _PairGenerator:
    """Sentence indices → (center, context) pairs with dynamic windows and
    frequent-word subsampling, batched (the role of AsyncSequencer +
    per-thread window loops in the reference)."""

    def __init__(self, window: int, keep_probs: np.ndarray, rs: np.random.RandomState):
        self.window = window
        self.keep = keep_probs
        self.rs = rs

    def generate(self, idx_seqs: Iterable[np.ndarray]):
        for idx in idx_seqs:
            if len(idx) < 2:
                continue
            keep = self.rs.rand(len(idx)) < self.keep[idx]
            idx = idx[keep]
            if len(idx) < 2:
                continue
            b = self.rs.randint(1, self.window + 1, len(idx))
            for i, center in enumerate(idx):
                lo = max(0, i - b[i])
                hi = min(len(idx), i + b[i] + 1)
                for j in range(lo, hi):
                    if j != i:
                        yield center, idx[j]

    def generate_windows(self, idx_seqs: Iterable[np.ndarray]):
        """CBOW windows (CBOW.java semantics): for each center position,
        yield (center, [context ids]) with the full dynamic window — the
        window AVERAGE predicts the center, not reversed skip-gram pairs."""
        for idx in idx_seqs:
            if len(idx) < 2:
                continue
            keep = self.rs.rand(len(idx)) < self.keep[idx]
            idx = idx[keep]
            if len(idx) < 2:
                continue
            b = self.rs.randint(1, self.window + 1, len(idx))
            for i, center in enumerate(idx):
                lo = max(0, i - b[i])
                hi = min(len(idx), i + b[i] + 1)
                ctx = [int(idx[j]) for j in range(lo, hi) if j != i]
                if ctx:
                    yield int(center), ctx


def _batched(gen, batch_size: int):
    buf_c, buf_t = [], []
    for c, t in gen:
        buf_c.append(c)
        buf_t.append(t)
        if len(buf_c) == batch_size:
            yield np.asarray(buf_c, np.int32), np.asarray(buf_t, np.int32)
            buf_c, buf_t = [], []
    if buf_c:
        yield np.asarray(buf_c, np.int32), np.asarray(buf_t, np.int32)


def _fast_pairs(idx_seqs, window: int, keep: np.ndarray,
                rs: np.random.RandomState):
    """Vectorized skip-gram pair generation: per sentence, same
    subsampling + dynamic-window SEMANTICS as _PairGenerator.generate (a
    pair (i, i±o) exists iff o <= b_i and in range) but built with per-
    offset numpy masks instead of a per-pair Python loop — ~50x the
    host-side throughput. Draw ORDER
    differs from the per-pair generator, so trajectories are not
    bit-identical across backends (the pair multiset per sentence is,
    given equal rng draws). Yields (centers, contexts) int32 arrays."""
    for idx in idx_seqs:
        if len(idx) < 2:
            continue
        kmask = rs.rand(len(idx)) < keep[idx]
        idx = idx[kmask]
        n = len(idx)
        if n < 2:
            continue
        b = rs.randint(1, window + 1, n)
        pos = np.arange(n)
        cs, ts = [], []
        for o in range(1, window + 1):
            sel = b >= o
            right = pos[sel & (pos + o < n)]
            left = pos[sel & (pos - o >= 0)]
            cs.append(idx[right])
            ts.append(idx[right + o])
            cs.append(idx[left])
            ts.append(idx[left - o])
        yield (np.concatenate(cs).astype(np.int32),
               np.concatenate(ts).astype(np.int32))


def _batched_arrays(gen, batch_size: int):
    """Re-chunk a stream of (centers, contexts) ARRAYS into batch_size
    pieces (array analogue of _batched)."""
    bufs_c, bufs_t, count = [], [], 0
    for c, t in gen:
        bufs_c.append(c)
        bufs_t.append(t)
        count += len(c)
        if count >= batch_size:
            cc = np.concatenate(bufs_c)
            tt = np.concatenate(bufs_t)
            while len(cc) >= batch_size:
                yield cc[:batch_size], tt[:batch_size]
                cc, tt = cc[batch_size:], tt[batch_size:]
            bufs_c, bufs_t, count = [cc], [tt], len(cc)
    if count:
        yield np.concatenate(bufs_c), np.concatenate(bufs_t)


def _batched_windows(gen, batch_size: int, max_width: int):
    """Batch (center, [contexts]) — or tagged (tag, center, [contexts]) —
    into padded [B,W] arrays + win_mask. Tagged items (the PV-DM doc id)
    yield (tags, centers, win, mask); untagged yield (centers, win, mask)."""

    def flush(tags, centers, ctxs):
        B = len(centers)
        win = np.zeros((B, max_width), np.int32)
        mask = np.zeros((B, max_width), np.float32)
        for r, ctx in enumerate(ctxs):
            L = min(len(ctx), max_width)
            win[r, :L] = ctx[:L]
            mask[r, :L] = 1.0
        out = (np.asarray(centers, np.int32), win, mask)
        return (np.asarray(tags, np.int32),) + out if tags else out

    tags, centers, ctxs = [], [], []
    for item in gen:
        if len(item) == 3:
            t, c, ctx = item
            tags.append(t)
        else:
            c, ctx = item
        centers.append(c)
        ctxs.append(ctx)
        if len(centers) == batch_size:
            yield flush(tags, centers, ctxs)
            tags, centers, ctxs = [], [], []
    if centers:
        yield flush(tags, centers, ctxs)


# ---------------------------------------------------------------------------
# SequenceVectors
# ---------------------------------------------------------------------------


class SequenceVectors:
    """Generic embedding trainer over element sequences
    (models/sequencevectors/SequenceVectors.java).

    ``sequences``: iterable of token lists (or a callable producing one per
    epoch). Algorithms: elements_learning = "skipgram" | "cbow";
    use_hierarchic_softmax switches HS on (negative=0) as in the reference.
    """

    def __init__(
        self,
        layer_size: int = 100,
        window: int = 5,
        negative: int = 5,
        use_hierarchic_softmax: bool = False,
        learning_rate: float = 0.025,
        min_learning_rate: float = 1e-4,
        min_word_frequency: int = 5,
        sample: float = 1e-3,
        epochs: int = 1,
        # pairs per fused device step; the step is scatter-add bound at
        # large batches. Raise toward
        # 65536 on big corpora to amortize dispatch.
        batch_size: int = 8192,
        elements_learning: str = "skipgram",
        seed: int = 12345,
        # "python": per-pair generator (reference-faithful draw order);
        # "numpy": vectorized per-offset masks, ~50x host throughput —
        # same pair distribution, different rng draw order (skip-gram only).
        # With "numpy", SG-NS training also runs scan_batches device steps
        # per dispatch (negatives drawn on-device, inverse-CDF over the
        # unigram table — same distribution as the host draw).
        pair_backend: str = "python",
        scan_batches: int = 64,
        # epoch-scan unroll factor: 4 = fastest warm epoch; 1 = ~halved
        # first-epoch XLA compile (see utils/compile_cache for the
        # cross-process amortization alternative)
        scan_unroll: int = 4,
    ):
        self.layer_size = layer_size
        self.window = window
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        self.lr = learning_rate
        self.min_lr = min_learning_rate
        self.min_word_frequency = min_word_frequency
        self.sample = sample
        self.epochs = epochs
        self.batch_size = batch_size
        self.elements_learning = elements_learning
        if pair_backend not in ("python", "numpy"):
            raise ValueError(f"pair_backend must be 'python' or 'numpy', got {pair_backend!r}")
        if scan_batches < 1:
            raise ValueError(f"scan_batches must be >= 1, got {scan_batches}")
        if scan_unroll < 1:
            raise ValueError(f"scan_unroll must be >= 1, got {scan_unroll}")
        self.pair_backend = pair_backend
        self.scan_batches = scan_batches
        self.scan_unroll = scan_unroll
        self.seed = seed
        self.vocab: Optional[VocabCache] = None
        self.params: Optional[dict] = None
        self._rs = np.random.RandomState(seed)
        self._step_cache: dict = {}

    # -- vocab + init ------------------------------------------------------
    def build_vocab(self, sequences: Iterable[Sequence[str]], special: Sequence[str] = ()):
        vc = VocabConstructor(self.min_word_frequency, tokenizer=_IdentityTok())
        self.vocab = vc.build(sequences, special=special)
        if self.use_hs:
            build_huffman(self.vocab)
        return self

    def _init_params(self):
        V, D = len(self.vocab), self.layer_size
        rs = np.random.RandomState(self.seed)
        p = {
            "syn0": jnp.asarray((rs.rand(V, D).astype(np.float32) - 0.5) / D),
            "syn1neg": jnp.asarray(np.zeros((V, D), np.float32)),
        }
        if self.use_hs:
            p["syn1"] = jnp.asarray(np.zeros((max(V - 1, 1), D), np.float32))
        self.params = p

    # -- training ----------------------------------------------------------
    def _jit_step(self, kind: str):
        if kind not in self._step_cache:
            fn = {"sg_ns": _sg_ns_step, "cbow_ns": _cbow_ns_step,
                  "sg_hs": _sg_hs_step, "cbow_hs": _cbow_hs_step,
                  "dm_ns": _dm_ns_step}[kind]
            self._step_cache[kind] = jax.jit(fn, donate_argnums=(0,))
        return self._step_cache[kind]

    def _index_sequences(self, sequences) -> List[np.ndarray]:
        out = []
        for seq in sequences:
            idx = [self.vocab.index_of(t) for t in seq]
            out.append(np.asarray([i for i in idx if i >= 0], np.int64))
        return out

    def fit(self, sequences) -> "SequenceVectors":
        seqs = sequences() if callable(sequences) else sequences
        seqs = list(seqs)
        if self.vocab is None:
            self.build_vocab(seqs)
        if self.params is None:
            self._init_params()
        self._run_epochs(self._index_sequences(seqs), self.epochs)
        return self

    def _run_epochs(self, idx_seqs, epochs: int, *, schedule_span: Optional[int] = None,
                    schedule_offset: int = 0) -> None:
        """Train ``epochs`` passes over already-indexed sequences against the
        EXISTING vocab/params (the distributed trainer calls this one round
        at a time between parameter-averaging steps).

        ``schedule_span``/``schedule_offset``: total epochs the linear lr
        decay spans and how many are already complete — lets a multi-round
        caller anneal ONCE across all rounds instead of saw-toothing."""
        keep = subsample_probs(self.vocab, self.sample)
        table = unigram_table(self.vocab)
        if self.use_hs:
            codes, points, hmask = huffman_tables(self.vocab)
            codes_j, points_j = jnp.asarray(codes), jnp.asarray(points)
            hmask_j = jnp.asarray(hmask)

        cum_dev = None  # unigram-table cumsum, uploaded once for all epochs
        span = schedule_span if schedule_span is not None else epochs
        pairs_per_epoch = sum(len(s) for s in idx_seqs) * self.window
        total_pairs_est = max(pairs_per_epoch * span, 1)
        seen = pairs_per_epoch * schedule_offset
        for _ in range(epochs):
            pg = _PairGenerator(self.window, keep, self._rs)
            if self.elements_learning == "cbow":
                # true CBOW (CBOW.java): the window AVERAGE predicts the
                # center — padded [B, 2*window] windows with win_mask.
                # NS and HS branches share the window batching; HS walks
                # the CENTER word's Huffman path.
                step = self._jit_step("cbow_hs" if self.use_hs else "cbow_ns")
                for centers, win, wmask in _batched_windows(
                    pg.generate_windows(idx_seqs), self.batch_size, 2 * self.window
                ):
                    frac = min(seen / total_pairs_est, 1.0)
                    lr = max(self.lr * (1.0 - frac), self.min_lr)
                    seen += len(centers)
                    if self.use_hs:
                        self.params, _ = step(
                            self.params, jnp.asarray(win), jnp.asarray(wmask),
                            codes_j[centers], points_j[centers], hmask_j[centers],
                            jnp.asarray(lr, jnp.float32),
                        )
                    else:
                        negs = self._draw_negatives(
                            table, (len(centers), self.negative))
                        self.params, _ = step(
                            self.params, jnp.asarray(win), jnp.asarray(wmask),
                            jnp.asarray(centers), jnp.asarray(negs),
                            jnp.asarray(lr, jnp.float32),
                        )
                continue
            if self.pair_backend == "numpy" and not self.use_hs:
                # epoch-scan fast path: chunks of scan_batches full batches
                # run as ONE device dispatch (lax.scan, on-device negatives)
                # — the leftover tail falls through to the per-batch path
                chunk = self.batch_size * self.scan_batches
                if "sg_ns_scan" not in self._step_cache:
                    self._step_cache["sg_ns_scan"] = jax.jit(
                        _sg_ns_epoch_scan, donate_argnums=(0,),
                        static_argnames=("negative", "unroll"))
                scan_step = self._step_cache["sg_ns_scan"]
                if cum_dev is None:
                    cum_dev = jnp.asarray(np.cumsum(table), jnp.float32)
                cum = cum_dev
                # separate key stream: drawing chunk keys from self._rs
                # would interleave with the (lazy) pair generator's draws
                # and break pair-stream reproducibility
                key_rs = np.random.RandomState(self._rs.randint(2 ** 31))
                tail_c: List[np.ndarray] = []
                tail_t: List[np.ndarray] = []
                for cc, tt in _batched_arrays(
                        _fast_pairs(idx_seqs, self.window, keep, self._rs),
                        chunk):
                    if len(cc) == chunk:
                        key = jax.random.PRNGKey(key_rs.randint(2 ** 31))
                        self.params, _ = scan_step(
                            self.params,
                            jnp.asarray(cc.reshape(self.scan_batches,
                                                   self.batch_size)),
                            jnp.asarray(tt.reshape(self.scan_batches,
                                                   self.batch_size)),
                            cum, key, jnp.asarray(self.lr, jnp.float32),
                            jnp.asarray(self.min_lr, jnp.float32),
                            float(seen), float(total_pairs_est),
                            negative=self.negative,
                            unroll=self.scan_unroll)
                        seen += len(cc)
                    else:
                        tail_c.append(cc)
                        tail_t.append(tt)
                # tail: re-chunk to batch_size for the per-batch path
                pair_stream = _batched_arrays(zip(tail_c, tail_t),
                                              self.batch_size)
            elif self.pair_backend == "numpy":
                pair_stream = _batched_arrays(
                    _fast_pairs(idx_seqs, self.window, keep, self._rs),
                    self.batch_size)
            else:
                pair_stream = _batched(pg.generate(idx_seqs), self.batch_size)
            for centers, contexts in pair_stream:
                frac = min(seen / total_pairs_est, 1.0)
                lr = max(self.lr * (1.0 - frac), self.min_lr)
                seen += len(centers)
                if self.use_hs:
                    step = self._jit_step("sg_hs")
                    self.params, _ = step(
                        self.params, jnp.asarray(centers),
                        codes_j[contexts], points_j[contexts], hmask_j[contexts],
                        jnp.asarray(lr, jnp.float32),
                    )
                else:
                    step = self._jit_step("sg_ns")
                    negs = self._draw_negatives(table, (len(centers), self.negative))
                    self.params, _ = step(
                        self.params, jnp.asarray(centers), jnp.asarray(contexts),
                        jnp.asarray(negs), jnp.asarray(lr, jnp.float32),
                    )

    def _draw_negatives(self, table: np.ndarray, shape) -> np.ndarray:
        # inverse-CDF sampling: identical distribution to
        # rs.choice(p=table) but ~100x faster at vocab 100K (choice-with-p
        # rebuilds its alias structures per call); cumsum cached per table
        cached = getattr(self, "_neg_cum", None)
        if cached is None or cached[0] is not table:
            cached = (table, np.cumsum(table))
            self._neg_cum = cached
        u = self._rs.random_sample(shape)
        return np.minimum(np.searchsorted(cached[1], u),
                          len(table) - 1).astype(np.int32)

    # -- lookup API (WordVectors interface) --------------------------------
    @property
    def syn0(self) -> np.ndarray:
        return np.asarray(self.params["syn0"])

    def has_word(self, word: str) -> bool:
        return self.vocab is not None and word in self.vocab

    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return self.syn0[i] if i >= 0 else None

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.get_word_vector(a), self.get_word_vector(b)
        if va is None or vb is None:
            return float("nan")
        denom = np.linalg.norm(va) * np.linalg.norm(vb)
        return float(va @ vb / denom) if denom > 0 else 0.0

    def words_nearest(self, word_or_vec, top_n: int = 10) -> List[str]:
        """Cosine-nearest words — ONE [V,D]x[D] matmul (MXU), not a VP-tree."""
        if isinstance(word_or_vec, str):
            v = self.get_word_vector(word_or_vec)
            exclude = {word_or_vec}
            if v is None:
                return []
        else:
            v = np.asarray(word_or_vec)
            exclude = set()
        m = self.syn0
        norms = np.linalg.norm(m, axis=1) * max(np.linalg.norm(v), 1e-12)
        sims = (m @ v) / np.maximum(norms, 1e-12)
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.word_at(int(i))
            if w not in exclude:
                out.append(w)
            if len(out) >= top_n:
                break
        return out


class _IdentityTok:
    def tokenize(self, s):
        return list(s) if not isinstance(s, str) else s.split()


# ---------------------------------------------------------------------------
# Word2Vec / ParagraphVectors / StaticWord2Vec
# ---------------------------------------------------------------------------


class Word2Vec(SequenceVectors):
    """models/word2vec/Word2Vec.java: SequenceVectors over tokenized
    sentences. ``fit(sentences)`` accepts strings or a sentence iterator."""

    def __init__(self, tokenizer_factory=None, **kw):
        super().__init__(**kw)
        self.tokenizer_factory = tokenizer_factory

    def _tokenize_all(self, sentences) -> List[List[str]]:
        from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizerFactory

        tok = self.tokenizer_factory or DefaultTokenizerFactory()
        out = []
        for s in sentences:
            out.append(tok.tokenize(s) if isinstance(s, str) else list(s))
        return out

    def build_vocab(self, sentences, special=()):
        return super().build_vocab(self._tokenize_all(sentences), special=special)

    def fit(self, sentences) -> "Word2Vec":
        seqs = sentences() if callable(sentences) else sentences
        return super().fit(self._tokenize_all(seqs))


class ParagraphVectors(Word2Vec):
    """models/paragraphvectors/ParagraphVectors.java: documents get their own
    vectors. ``sequence_learning="dbow"`` (default, the reference's DBOW
    impl: the label vector predicts each word) or ``"dm"`` (PV-DM,
    learning/impl/sequence/DM.java: doc vector + window average predict the
    center word)."""

    LABEL_PREFIX = "__label__"

    def __init__(self, sequence_learning: str = "dbow", **kw):
        kw.setdefault("min_word_frequency", 1)
        super().__init__(**kw)
        if sequence_learning not in ("dbow", "dm"):
            raise ValueError(f"sequence_learning must be 'dbow' or 'dm', "
                             f"got {sequence_learning!r}")
        self.sequence_learning = sequence_learning
        self.labels: List[str] = []

    def fit_documents(self, docs: Sequence[Tuple[str, str]]) -> "ParagraphVectors":
        """docs: (text, label) pairs (LabelAwareIterator surface)."""
        texts = [t for t, _ in docs]
        self.labels = [self.LABEL_PREFIX + l for _, l in docs]
        token_seqs = self._tokenize_all(texts)
        # vocab over words + labels (labels as special tokens)
        super(Word2Vec, self).build_vocab(token_seqs, special=tuple(self.labels))
        self._init_params()
        table = unigram_table(self.vocab)
        if self.sequence_learning == "dm":
            self._fit_dm(token_seqs, table)
        else:
            self._fit_dbow(token_seqs, table)
        # words also train among themselves (reference trainElementsVectors)
        super(Word2Vec, self).fit(token_seqs)
        return self

    def _fit_dbow(self, token_seqs, table):
        # DBOW: every (label, word) pair is a skip-gram pair
        step = self._jit_step("sg_ns")
        lr = self.lr
        for ep in range(self.epochs):
            pairs_c, pairs_t = [], []
            for label, toks in zip(self.labels, token_seqs):
                li = self.vocab.index_of(label)
                for t in toks:
                    ti = self.vocab.index_of(t)
                    if ti >= 0:
                        pairs_c.append(li)
                        pairs_t.append(ti)
            order = self._rs.permutation(len(pairs_c))
            pc = np.asarray(pairs_c, np.int32)[order]
            pt = np.asarray(pairs_t, np.int32)[order]
            for i in range(0, len(pc), self.batch_size):
                c = pc[i:i + self.batch_size]
                t = pt[i:i + self.batch_size]
                negs = self._draw_negatives(table, (len(c), self.negative))
                self.params, _ = step(
                    self.params, jnp.asarray(c), jnp.asarray(t), jnp.asarray(negs),
                    jnp.asarray(lr, jnp.float32),
                )
            lr = max(lr * 0.9, self.min_lr)

    def _fit_dm(self, token_seqs, table):
        # PV-DM: (doc, window) -> center. Windows per document, batched by
        # the shared padded-window batcher with the doc's label row as tag.
        step = self._jit_step("dm_ns")
        keep = subsample_probs(self.vocab, self.sample)
        W = 2 * self.window
        lr = self.lr
        for ep in range(self.epochs):
            pg = _PairGenerator(self.window, keep, self._rs)
            items = []  # (doc_id, center, ctx)
            for label, toks in zip(self.labels, token_seqs):
                li = self.vocab.index_of(label)
                idx = np.asarray(
                    [i for i in (self.vocab.index_of(t) for t in toks) if i >= 0],
                    np.int64)
                for center, ctx in pg.generate_windows([idx]):
                    items.append((li, center, ctx))
            self._rs.shuffle(items)
            for docs, centers, win, mask in _batched_windows(
                    iter(items), self.batch_size, W):
                negs = self._draw_negatives(table, (len(centers), self.negative))
                self.params, _ = step(
                    self.params, jnp.asarray(docs), jnp.asarray(win),
                    jnp.asarray(mask), jnp.asarray(centers), jnp.asarray(negs),
                    jnp.asarray(lr, jnp.float32),
                )
            lr = max(lr * 0.9, self.min_lr)

    def get_label_vector(self, label: str) -> Optional[np.ndarray]:
        return self.get_word_vector(self.LABEL_PREFIX + label)

    def infer_vector(self, text: str, steps: int = 20) -> np.ndarray:
        """Infer a vector for unseen text: average of known word vectors
        refined by DBOW steps against a frozen vocab (inferVector)."""
        toks = self._tokenize_all([text])[0]
        idx = np.asarray([self.vocab.index_of(t) for t in toks], np.int64)
        idx = idx[idx >= 0]
        if len(idx) == 0:
            return np.zeros(self.layer_size, np.float32)
        v = self.syn0[idx].mean(axis=0)
        syn1 = np.asarray(self.params["syn1neg"])
        lr = self.lr
        rs = np.random.RandomState(0)
        table = unigram_table(self.vocab)
        for _ in range(steps):
            for t in idx:
                negs = rs.choice(len(table), size=self.negative, p=table)
                tv = syn1[t]
                g = (1.0 / (1.0 + np.exp(-v @ tv))) - 1.0
                d = g * tv
                for nidx in negs:
                    nv = syn1[nidx]
                    gn = 1.0 / (1.0 + np.exp(-v @ nv))
                    d = d + gn * nv
                v = v - lr * d
            lr *= 0.9
        return v.astype(np.float32)

    def similarity_to_label(self, text: str, label: str) -> float:
        v = self.infer_vector(text)
        lv = self.get_label_vector(label)
        if lv is None:
            return float("nan")
        denom = np.linalg.norm(v) * np.linalg.norm(lv)
        return float(v @ lv / denom) if denom > 0 else 0.0


class StaticWord2Vec:
    """Inference-only word vectors (models/word2vec/StaticWord2Vec.java):
    frozen table + lookup/similarity, no trainer state."""

    def __init__(self, vocab: VocabCache, vectors: np.ndarray):
        self.vocab = vocab
        self.syn0 = np.asarray(vectors, np.float32)

    @staticmethod
    def from_model(m: SequenceVectors) -> "StaticWord2Vec":
        return StaticWord2Vec(m.vocab, m.syn0)

    def get_word_vector(self, word: str):
        i = self.vocab.index_of(word)
        return self.syn0[i] if i >= 0 else None

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.get_word_vector(a), self.get_word_vector(b)
        if va is None or vb is None:
            return float("nan")
        denom = np.linalg.norm(va) * np.linalg.norm(vb)
        return float(va @ vb / denom) if denom > 0 else 0.0

    def words_nearest(self, word: str, top_n: int = 10) -> List[str]:
        sv = SequenceVectors.__new__(SequenceVectors)
        sv.vocab = self.vocab
        sv.params = {"syn0": jnp.asarray(self.syn0)}
        return SequenceVectors.words_nearest(sv, word, top_n)
