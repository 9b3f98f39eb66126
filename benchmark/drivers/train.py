"""The train driver: one ``MultiLayerNetwork`` through ``fit()`` on a feed of
batches, as a user trains (site ``mln.step``, default settings).

Set-up builds the one model, fills it with the benchmark's weights for the
seed, and drives it through its first steps with the window's own call and
feed; the window goes on with the same object. Every step ends in the loss
fetch that ``fit()`` makes for its listener, so a stall inside the window
counts. After the window the plain reference follows the first steps and the
driver compares: each step's loss, the first gradient's norm leaf by leaf (read
from Adam's first moment after one step), that gradient itself on the first,
the middle and the last block and the small leaves outside the blocks (kept
on the host from set-up), and the norm of each leaf's change after the
last warm-up step.
"""

from __future__ import annotations

import time
from typing import Dict, Iterator, List, Tuple

import numpy as np

from benchmark.harness import compare, spec

END_TO_END = ("train_tokens_per_s",)
WARM_STEPS = 3          # the steps the reference follows


def make_batches(traffic: dict, vocab: int, seed: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``pool`` batches of ids uniform over the vocabulary, every row
    different, labels the ids shifted by one (sparse int32)."""
    rs = np.random.default_rng(int(seed))
    out = []
    for _ in range(int(traffic["pool"])):
        ids = rs.integers(0, vocab, (int(traffic["batch"]),
                                     int(traffic["seq_len"])), dtype=np.int32)
        out.append((ids, np.roll(ids, -1, axis=1)))
    return out


class _Listener:
    """What ``fit()`` calls after each step's loss fetch. Ends the feed when
    the window's time is up, and lets the tracer start and stop between two
    steps."""

    def __init__(self):
        self.losses: List[float] = []
        self.ends: List[float] = []
        self.deadline = None
        self.t0 = None
        self.tracer = None
        self.stop = False

    def on_epoch_start(self, model, epoch):
        pass

    def on_epoch_end(self, model, epoch):
        pass

    def on_gradient_calculation(self, model, iteration):
        pass

    def iteration_done(self, model, iteration, score, batch_size=0):
        now = time.perf_counter()
        self.losses.append(float(score))
        self.ends.append(now)
        if self.deadline is not None:
            if self.tracer is not None:
                self.tracer.poll(now - self.t0)
            if now >= self.deadline:
                self.stop = True


def _feed(batches, listener: _Listener, start: int, count=None) -> Iterator:
    """The window's feed: the pool in order, from ``start``, until the
    listener says stop (or ``count`` batches, for the warm-up steps)."""
    i = start
    while not listener.stop and (count is None or i < start + count):
        yield batches[i % len(batches)]
        i += 1


def setup(ctx) -> dict:
    import jax

    cell, cfg = ctx["cell"], ctx["cell"]["config"]
    fam = spec.module("families", cfg["family"])
    ref = spec.module("reference", cfg["reference"])
    words = ref.seed_words(ctx["seed"])
    t = time.perf_counter()
    model = fam.new_model(cfg, words)
    jax.block_until_ready(model.params)
    ctx["log"](f"weights: {model.num_params()} parameters ({cfg['dtype']}) on "
               f"the device in {time.perf_counter() - t:.1f}s")
    batches = make_batches(cell["traffic"], int(cfg["vocab_size"]), ctx["seed"])
    listener = _Listener()
    model.set_listeners(listener)

    norms = jax.jit(lambda o: fam.sq_norms(
        cfg, tuple(s["m"] for s in o)))
    change = jax.jit(lambda p, w: fam.change_sq_norms(cfg, p, w, model.dtype))
    prog: Dict[str, dict] = {}
    t = time.perf_counter()
    for k in range(WARM_STEPS):
        model.fit(_feed(batches, listener, k, 1))
        if k == 0:
            # m = (1 - beta1) g after one step from m = 0
            g_of_m = 1.0 / (1.0 - ref.ADAM["beta1"])
            prog["grad_norms"] = {
                n: np.sqrt(np.asarray(v)) * g_of_m
                for n, v in norms(model.opt_state).items()}
            prog["grad_leaves"] = {
                n: v * np.float32(g_of_m) for n, v in fam.kept_leaves(
                    cfg, tuple(s["m"] for s in model.opt_state),
                    grad_layers(cfg)).items()}
            ctx["log"](f"first step (compile or cache read + run): "
                       f"{time.perf_counter() - t:.1f}s")
    prog["change_norms"] = {n: np.sqrt(np.asarray(v))
                            for n, v in change(model.params, words).items()}
    prog["losses"] = list(listener.losses)
    if len(prog["losses"]) != WARM_STEPS:
        raise RuntimeError(f"fit() made {len(prog['losses'])} steps of "
                           f"{WARM_STEPS} in set-up")
    return {"model": model, "batches": batches, "listener": listener,
            "prog": prog, "words": words, "fam": fam, "ref": ref}


def grad_layers(cfg: dict) -> tuple:
    """The blocks whose first gradient is compared whole: the first, the
    middle and the last."""
    L = int(cfg["n_layer"])
    return tuple(sorted({0, L // 2, L - 1}))


def window(ctx, s: dict) -> dict:
    from deeplearning4j_tpu.utils import bucketing

    model, listener = s["model"], s["listener"]
    traffic = ctx["cell"]["traffic"]
    tel = bucketing.telemetry()
    traces0 = tel.compiles("mln.step")
    compiles0 = ctx["compiles"].count
    listener.losses.clear()
    listener.ends.clear()
    listener.tracer = ctx["tracer"]
    t0 = listener.t0 = time.perf_counter()
    listener.deadline = t0 + ctx["seconds"]
    model.fit(_feed(s["batches"], listener, WARM_STEPS))
    ctx["tracer"].stop()
    ends = list(listener.ends)
    if not ends:
        raise RuntimeError("no step ended in the window")
    window_s = ends[-1] - t0
    per_step = int(traffic["batch"]) * int(traffic["seq_len"])
    tokens = len(ends) * per_step
    # the traced run's host-clock facts stop where the profiler starts: its
    # start and stop stretch the steps around them
    cut = ctx["tracer"].started_at
    clean = [e for e in ends if cut is None or e <= cut] or ends[:1]
    steps_ms = np.diff([t0] + clean) * 1e3
    bad = [l for l in listener.losses if not np.isfinite(l)]
    return {
        "attempted": len(ends), "failed": len(bad),
        "metrics": {"train_tokens_per_s": tokens / window_s},
        "facts": {
            "window_s": clean[-1] - t0, "tokens": len(clean) * per_step,
            "steps": len(clean),
            "step_ms": [float(x) for x in steps_ms],
            "compiles_in_window": (ctx["compiles"].count - compiles0)
            + (tel.compiles("mln.step") - traces0),
            "batch": int(traffic["batch"]), "seq_len": int(traffic["seq_len"]),
        },
    }


def free(s: dict) -> None:
    """Drop the program's state so that the reference fits beside nothing."""
    model = s.pop("model")
    model.set_listeners()
    model.params = model.opt_state = model.state = None
    model._clear_compiled()
    del model


def check(ctx, s: dict) -> dict:
    """The reference's first steps against the program's."""
    cell, cfg = ctx["cell"], ctx["cell"]["config"]
    ref, prog = s["ref"], s["prog"]
    chk = cell["check"]
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["dtype"])
    make = jax.jit(lambda w: ref.make_weights(cfg, w, dtype))
    steps = dict(batches=s["batches"][:WARM_STEPS],
                 lr=float(cfg["updater"]["lr"]),
                 rows=int(chk.get("reference_rows", 2)),
                 keep_layers=grad_layers(cfg))
    t = time.perf_counter()
    r = ref.train_steps(cfg, make(s["words"]), **steps)
    ctx["log"](f"reference: {WARM_STEPS} steps in {time.perf_counter() - t:.1f}s")
    out = judge(prog, r, chk["limits"])
    for control in ctx.get("controls", ()):
        # a control, or a planted fault: the reference in the lower precision
        # (or broken), put in the program's place and judged the same way
        kw = ({"faults": (control[6:],)} if control.startswith("fault:")
              else {"lowp": control})
        out.setdefault("controls", {})[control] = judge(
            ref.train_steps(cfg, make(s["words"]), **steps, **kw), r,
            chk["limits"])
    return out


def judge(prog: dict, r: dict, limits: dict) -> dict:
    numbers, notes = {}, {}
    for i, (a, b) in enumerate(zip(prog["losses"], r["losses"])):
        numbers[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    g_ref = compare.flatten(r["grad_norms"])
    numbers["grad_norm_gap"], notes["grad_norm_gap"] = compare.worst_norm_gap(
        compare.flatten(prog["grad_norms"]), g_ref)
    numbers["grad_diff_norm"], notes["grad_diff_norm"] = \
        compare.worst_diff_norm(prog["grad_leaves"], r["grad_leaves"])
    # leaves whose gradient is nought to rounding in the reference (a key's
    # bias under softmax) move under Adam by round-off alone: left out of the
    # change by a rule on the reference's gradient, not by name
    g_floor = 1e-3 * float(np.median(list(g_ref.values())))
    skip = {k for k, v in g_ref.items() if v < g_floor}
    numbers["change_norm_gap"], notes["change_norm_gap"] = \
        compare.worst_norm_gap(compare.flatten(prog["change_norms"]),
                               compare.flatten(r["change_norms"]), skip)
    return compare.verdict(numbers, limits, notes)
