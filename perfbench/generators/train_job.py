"""Traffic kind ``train_job``: a training job fed from a ring of seeded
host batches through the family's one compiled step.

Set-up builds ONE object (the compiled step with its state), drives it
through its first ``check_steps`` steps by the window's own call and
feed, compares those with the plain reference (run first, while the
device holds nothing of the program's, in blocks of rows), and hands
the same object to the window. The window keeps the device fed two
steps ahead and stops its clock after ``block_until_ready`` on the
last step's loss.
"""
import gc
import importlib
import math
import statistics
import time

from perfbench import harness

PIPELINE_DEPTH = 2


def worst_leaf_gap(got, want, skip=()):
    """The worst leaf's gap between the program's norm and the
    reference's (not the norm of a difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger — some gradients are all but zero."""
    med = statistics.median(want.values())
    worst, where = 0.0, None
    for n, w in want.items():
        if n in skip:
            continue
        gap = abs(got[n] - w) / max(w, med)
        if gap >= worst:
            worst, where = gap, n
    return worst, where


def rel_gaps(got, want):
    """Each followed step's loss against the reference's."""
    return [abs(a - b) / abs(b)
            for a, b in zip(got["losses"], want["losses"])]


def null_gradient_leaves(grad_norm):
    """Leaves whose true gradient is zero by the mathematics (a key
    bias shifts every score of a row alike and softmax ignores it).
    Their reference gradient is rounding noise; Adam divides noise by
    its own size, so their parameters' change says nothing."""
    med = statistics.median(grad_norm.values())
    return {n for n, v in grad_norm.items() if v < 1e-6 * med}


def compare_with_reference(checks, limits, got, want):
    """The numbers of "How correct is decided", training: the first
    step's loss; the norm of the first gradient as the optimizer gets
    it, by the worst weight matrix (the number a lower precision fails;
    the one-dimensional leaves are left out: the two-element NSP bias's
    gradient all but cancels and its norm swings 10x from seed to seed
    in sound runs); the parameters' change after the followed steps by
    the worst leaf.

    The later steps' losses are reported and not compared: they ride on
    weights stepped in bfloat16 through AdamW's first, sign-like steps,
    where the loss jumps by up to 2.6, and their gap has no upper
    reading (the fp8 control reads under the sound runs' largest:
    PERF.md, section 2), so a limit on it could only fail sound runs."""
    # the first loss is at the seeded weights
    checks.at_most("loss_step1_rel_gap", rel_gaps(got, want)[0],
                   limits["loss_rel_gap_first"])
    vectors = set(want["grad_norm"]) - set(want["matrices"])
    g, where = worst_leaf_gap(got["grad_norm"], want["grad_norm"],
                              skip=vectors)
    checks.at_most(f"first_grad_norm_worst_matrix_gap[{where}]", g,
                   limits["grad_norm_gap"])
    skip = null_gradient_leaves(want["grad_norm"])
    c, where = worst_leaf_gap(got["change_norm"], want["change_norm"],
                              skip)
    checks.at_most(f"param_change_norm_worst_leaf_gap[{where}]", c,
                   limits["change_norm_gap"])


def follow_program(trainer, cfg, seed, batches, devices):
    """The program's side of the comparison, through the window's own
    call: losses of the first steps, the first gradient's leaf norms,
    the leaf norms of the parameters' change."""
    ref = importlib.import_module(
        "perfbench.reference." + cfg["family"])
    losses = []
    grad_norm = None
    for i, batch in enumerate(batches):
        losses.append(float(trainer(batch)))
        if i == 0:
            grad_norm = trainer.first_grad_norms()
    w0 = ref.make_weights(cfg, seed, devices[0])
    change = trainer.change_norms(w0)
    del w0
    return {"losses": losses, "grad_norm": grad_norm,
            "change_norm": change}


def run(cell, seed, seconds, tracer, meter, devices, t_start):
    import jax

    cfg, job = cell.config, cell.traffic
    family = cell.family()
    ref = importlib.import_module("perfbench.reference." + cfg["family"])
    checks = harness.Checks()
    n_check = job["check_steps"]

    # what both sides are given
    plan_dp = job.get("plan", {}).get("dp", 1)
    batch_rows = job["per_chip_batch"] * plan_dp
    ring = family.make_batches(cfg, job, seed, job["ring"], batch_rows)

    # the reference first, while the device holds nothing of the
    # program's; its seconds are not set-up
    t_ref = time.perf_counter()
    w0 = ref.make_weights(cfg, seed, devices[0])
    want = ref.follow(cfg, job, w0, ring[:n_check], job["optimizer"],
                      job["reference_block_rows"])
    del w0
    gc.collect()
    reference_s = time.perf_counter() - t_ref
    tracer.phases.add("reference", reference_s)
    harness.say("reference", seconds=round(reference_s, 2),
                losses=[round(v, 5) for v in want["losses"]])

    # ONE object: built, checked through its first steps, then timed
    trainer = family.build(cfg, job, seed, devices)
    fallbacks0 = sum(trainer.kernel_fallbacks().values())
    got = follow_program(trainer, cfg, seed, ring[:n_check], devices)
    compare_with_reference(checks, job["limits"], got, want)
    harness.say("followed", losses=[round(v, 5) for v in got["losses"]],
                rel_gaps=[float(f"{v:.3g}") for v in rel_gaps(got, want)])
    # a few more steps so every buffer of the steady state exists
    i = n_check
    for _ in range(job["warm_steps"]):
        loss = trainer(ring[i % len(ring)])
        i += 1
    first_window_loss = float(loss)
    setup_s = time.perf_counter() - t_start - reference_s
    harness.say("setup", setup_s=round(setup_s, 2), **meter.since((0, 0, 0)))

    # the window
    work = {}
    step_ends = []
    inflight = []
    losses = []
    mark = meter.mark()
    with tracer.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            batch = ring[i % len(ring)]
            with tracer.span("train.step"):
                inflight.append(trainer(batch))
            for k, v in family.batch_work(batch).items():
                work[k] = work.get(k, 0) + v
            i += 1
            if len(inflight) > PIPELINE_DEPTH:
                with tracer.span("train.wait"):
                    losses.append(float(inflight.pop(0)))
                step_ends.append(time.perf_counter())
        for x in inflight:
            jax.block_until_ready(x)
            losses.append(float(x))
            step_ends.append(time.perf_counter())
        window_s = time.perf_counter() - t0
    built = meter.since(mark)["builds"]
    peak = harness.memory_peak_bytes(devices)

    steps = len(losses)
    # the window's losses are reported, not judged: a loss that has to
    # fall over the window has no reference and no control (PERF.md,
    # section 2); one that is no number is a fault whatever the seed
    checks.equal("nonfinite_losses_in_window",
                 sum(1 for v in losses if not math.isfinite(v)), 0)
    checks.equal("kernel_fallbacks_in_run",
                 sum(trainer.kernel_fallbacks().values()) - fallbacks0, 0)
    checks.at_least("steps_in_window", steps, job["min_steps"])
    harness.say("window", steps=steps, seconds=round(window_s, 3),
                tokens=work.get("tokens"),
                loss_first=round(first_window_loss, 4),
                loss_last=round(losses[-1], 4) if steps else None,
                loss_max=round(max(losses), 4) if steps else None)

    gaps = [b - a for a, b in zip(step_ends, step_ends[1:])]
    return {
        "quantities": {
            "train_tok_s_chip": work["tokens"] / window_s / trainer.chips,
        },
        "spans": {"step": gaps},
        "counters": {"steps": steps, "chips": trainer.chips,
                     "memory_peak_bytes": peak},
        "work": work,
        "attempted": steps,
        "failed": 0,
        "checks": checks,
        "setup_s": setup_s,
        "window_s": window_s,
        "window_builds": built,
        "memory_peak_bytes": peak,
    }
