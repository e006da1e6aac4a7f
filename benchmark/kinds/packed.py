"""Traffic kind ``packed``: training on packed rows. Every step takes ``rows``
rows of ``tokens_per_row`` fresh token ids drawn from ``--seed``; the work per
step is fixed by the traffic file.

Set-up builds ONE program (the compiled step with its state), drives it from
the seed through its first three steps, reading what ``correct`` compares, and
hands that same object to the window. The reference follows those three steps
once the window has closed and the program's state is freed.
"""

from __future__ import annotations

import statistics

import numpy as np

from benchmark import window

FIRST_STEPS = 3
# a leaf whose reference gradient is nought to rounding moves under Adam by
# round-off alone: left out of the change by this rule, never by name
SILENT_LEAF = 1e-3


def worst_leaf_gap(got: dict, ref: dict, leaves=None) -> float:
    """Largest gap between the program's norm and the reference's over the
    leaves, each against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    med = statistics.median(ref.values())
    return max(abs(got[k] - ref[k]) / max(ref[k], med) for k in (leaves or ref))


def compare(losses, grad_norms, change_norms, ref: dict) -> list:
    """[(name, value)] of every number read; the cell's limits file says which
    are compared. One step's loss gap is bf16 noise that swings thirtyfold from
    seed to seed, so the three steps' gaps are read by their mean (PERF.md has
    the readings, and why the 7B train cell sets no limit on it)."""
    steps = [abs(a - b) / abs(b) for a, b in zip(losses, ref["loss"])]
    out = [("loss_gap_mean", sum(steps) / len(steps)),
           ("grad_norm_gap", worst_leaf_gap(grad_norms, ref["grad_norm"]))]
    med = statistics.median(ref["grad_norm"].values())
    moving = [k for k, g in ref["grad_norm"].items() if g >= SILENT_LEAF * med]
    out.append(("change_norm_gap", worst_leaf_gap(change_norms, ref["change_norm"], moving)))
    return out


def first_steps(prog, batches):
    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        losses.append(float(prog(b).numpy()))
        if i == 0:
            grad_norms = prog.grad_norms()
    return losses, grad_norms, prog.change_norms()


def run(ctx) -> dict:
    cfg, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    rows, length = int(traffic["rows"]), int(traffic["tokens_per_row"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])

    def next_batch():
        return rng.integers(0, cfg["vocab_size"], (rows, length)).astype(np.int32)

    prog = ctx.family.build_train(cfg, seed)
    ctx.log("program built")
    batches = [next_batch() for _ in range(FIRST_STEPS)]
    losses, grad_norms, change_norms = first_steps(prog, batches)
    ctx.log("first steps driven and read")
    for _ in range(int(traffic["warm_steps"])):
        float(prog(next_batch()).numpy())

    span = ctx.span
    losses_in_window = []
    # one step stays in flight: step i+1 is dispatched before step i's loss is
    # fetched, as a loop that logs every loss one step late does. Each fetch is a
    # barrier that marks the end of one whole step, and the device never waits
    # for the host between steps, so host jitter stays out of a device-bound rate.
    in_flight = [prog(next_batch())]

    def step(i):
        with span("train.next_batch"):
            batch = next_batch()
        with span("train.step_call"):
            following = prog(batch)
        with span("train.loss_fetch"):
            losses_in_window.append(float(in_flight[0].numpy()))
        in_flight[0] = following

    compile_before = ctx.compile_stats()
    beat, gcs = window.Heartbeat(), window.GcWatch()
    ctx.trace_open()
    step(-1)                  # the opening barrier (after the profiler's start): step 0 is in flight
    del losses_in_window[:]
    beat.start()
    with gcs:
        ctx.mark_window_open()
        stamps = window.run_steps(step, ctx.seconds)
        ctx.mark_window_close()
    beat.stop()
    ctx.trace_close()
    compile_after = ctx.compile_stats()
    float(in_flight[0].numpy())              # the step left in flight; not counted
    ctx.read_memory_peak()
    prog.free()
    ctx.log("window closed, program freed")
    gaps = window.gap_summary(stamps)
    for line in window.stall_lines("loss scalars", gaps, None, beat.late_wakes, gcs.long, stamps[0], stamps[-1],
                                   ctx.at_open, ctx.at_close):
        ctx.log(line)

    ref = ctx.family.train_reference(cfg, seed, batches)
    ctx.log("reference done; loss by step, program " + " ".join(f"{x:.6f}" for x in losses)
            + " reference " + " ".join(f"{x:.6f}" for x in ref["loss"]))
    checks = compare(losses, grad_norms, change_norms, ref)
    finite = all(np.isfinite(losses_in_window))
    tokens_per_step = rows * length
    return {
        "attempted": len(stamps) - 1,
        "failed": 0 if finite else sum(1 for x in losses_in_window if not np.isfinite(x)),
        "end_to_end": {"train_tokens_per_s": window.rate(stamps, tokens_per_step)},
        "checks": checks,
        "obs": {"stamps": stamps, "gaps": gaps, "tokens_per_step": tokens_per_step, "rows": rows,
                "tokens_per_row": length, "compile_before": compile_before,
                "compile_after": compile_after, "span_names":
                ("train.next_batch", "train.step_call", "train.loss_fetch"),
                "unattributed": "unattributed"},
    }
