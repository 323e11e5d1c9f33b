"""``kind: train``.  Set-up builds ONE program (the compiled step with
its state), drives it from the seed through its first steps, whose
losses, first gradient and parameter change are what ``correct``
compares, and hands the same object to the window.  The window feeds
host batches round robin, their h2d inside the step, keeps two steps in
flight, and closes with ``block_until_ready`` on the last.  The adapter
is handed every device of the cell and the mix's ``mesh`` (absent: one
device), which says how it uses them."""
import collections
import time

import numpy as np

from .. import check, harness, traffic

END_TO_END = ("train_tokens_per_s",)
CHECKED_STEPS = 3


def first_steps(program, ref, dims, seed, batches):
    """Steps 1 to 3 of the program from the seed's weights: what the
    reference follows."""
    weights = ref.init_weights(dims, seed)
    program.load_weights(weights)
    del weights
    losses = [float(program.step(batches[0]))]
    grad_norms = program.first_grad_norms()
    for k in range(1, CHECKED_STEPS):
        losses.append(float(program.step(batches[k])))
    change = program.change_norms(ref.init_weights(dims, seed))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def window(program, batches, seconds, in_flight=2):
    """Step for ``seconds`` seconds.  Returns (steps, window seconds,
    the steps' losses)."""
    import jax
    pending = collections.deque()
    losses = []
    n = 0
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("pb.step"):
            loss = program.step(batches[n % len(batches)])
        n += 1
        pending.append(loss)
        losses.append(loss)
        if len(pending) > in_flight:
            with jax.profiler.TraceAnnotation("pb.wait_step"):
                pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
    with jax.profiler.TraceAnnotation("pb.wait_step"):
        loss.block_until_ready()
    return n, time.perf_counter() - t0, losses


def run(ctx):
    import jax
    cell, cfg, dims, args = ctx.cell, ctx.cfg, ctx.dims, ctx.args
    tr = ctx.mix
    ref = harness.module("reference", cfg["reference"])
    adapter = harness.module("adapters", cfg["adapter"])
    batches = traffic.mlm_batches(tr, dims["vocab_size"], args.seed)
    program = adapter.build(dict(cfg, use_flash=tr.get("use_flash", False)),
                            dims, batches[0], ctx.devices,
                            tr.get("mesh", {}))
    t_built = time.perf_counter()
    prog = first_steps(program, ref, dims, args.seed, batches)
    t_checked = time.perf_counter()
    for k in range(CHECKED_STEPS, tr["warmup_steps"]):
        program.step(batches[k % len(batches)]).block_until_ready()
    programs = program.programs()
    ctx.note(f"set-up: build {t_built - ctx.t_start:.1f}s, weights and "
             f"first steps {t_checked - t_built:.1f}s, warm-up "
             f"{time.perf_counter() - t_checked:.1f}s")

    profiler = harness.Profiler(cell["name"]) if args.trace else None
    seconds = args.seconds
    if profiler:
        seconds = min(seconds, cell["trace_seconds"])
        profiler.start()
    setup_s = time.perf_counter() - ctx.t_start
    if profiler:
        with jax.profiler.TraceAnnotation("pb.window"):
            steps, window_s, losses = window(program, batches, seconds)
        profiler.stop()
    else:
        steps, window_s, losses = window(program, batches, seconds)
    compiled_in_window = program.programs() - programs
    losses = np.asarray(jax.device_get(losses), np.float64)
    memory_peak = harness.memory_peak(ctx.devices)
    program.free()
    del program

    tokens = steps * tr["batch"] * tr["seqlen"]
    facts = {"steps": steps, "window_s": window_s, "traffic": tr,
             "tokens": tokens}
    metrics = {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s}

    t_ref = time.perf_counter()
    want = ref.train_steps(dims, cfg["optimizer"], args.seed,
                           batches[:CHECKED_STEPS], tr["reference_rows"])
    ctx.note(f"reference: {time.perf_counter() - t_ref:.1f}s after the "
             f"window")
    numbers, where = check.train_numbers(prog, want, ref.leaf_sizes(dims))
    numbers["compiled_in_window"] = float(compiled_in_window)
    numbers["nonfinite_losses"] = float((~np.isfinite(losses)).sum())
    ctx.note(f"first steps: program losses {prog['losses']}, reference "
             f"{want['losses']}; worst leaves {where}")
    return {"metrics": metrics, "facts": facts, "numbers": numbers,
            "attempted": steps,
            "failed": int((~np.isfinite(losses)).sum()),
            "memory_peak": memory_peak, "profiler": profiler}
