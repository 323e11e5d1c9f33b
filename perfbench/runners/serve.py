"""``kind: serve``.  A closed loop: ``clients`` threads, each sending
its next request when the last one returned, every token streamed
through ``on_token`` and stamped by the host's clock as it lands.  The
clients start before the window (the lead-in, part of set-up), so the
window opens on a system in its steady state; when it closes they
finish what is in flight and stop.  After that, and after the
program's state is freed, the reference scores a sample of the
requests the window finished."""
import threading
import time

import numpy as np

from .. import harness, traffic

END_TO_END = ("serve_tokens_per_s", "tpot_p95_ms")
REQUEST_TIMEOUT_S = 120.0


class Record:
    __slots__ = ("prompt", "new_tokens", "t_submit", "t_tokens", "tokens",
                 "out", "error", "t_done")

    def __init__(self, prompt, new_tokens):
        self.prompt, self.new_tokens = prompt, new_tokens
        self.t_tokens, self.tokens = [], []
        self.out = self.error = self.t_done = self.t_submit = None

    def on_token(self, token):
        self.t_tokens.append(time.perf_counter())
        self.tokens.append(int(token))

    @property
    def failed(self):
        return (self.error is not None or self.out is None
                or len(self.out) != self.new_tokens
                or self.tokens != [int(t) for t in self.out])


def serve_one(program, rec):
    import jax
    rec.t_submit = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("pb.generate"):
            rec.out = np.asarray(program.generate(
                rec.prompt, rec.new_tokens, rec.on_token,
                REQUEST_TIMEOUT_S))
    except Exception as e:          # noqa: BLE001 — counted as failed
        rec.error = e
    rec.t_done = time.perf_counter()
    return rec


class Clients:
    """The closed loop.  ``done`` fills as requests end."""

    def __init__(self, program, per_client):
        self.program = program
        self.done = []
        self.lock = threading.Lock()
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._client, args=(reqs,),
                                         daemon=True)
                        for reqs in per_client]

    def _client(self, requests):
        while not self.stop.is_set():
            prompt, new_tokens = next(requests)
            rec = serve_one(self.program, Record(prompt, new_tokens))
            with self.lock:
                self.done.append(rec)

    def start(self):
        for t in self.threads:
            t.start()

    def wait_for(self, n_done, timeout):
        end = time.perf_counter() + timeout
        while time.perf_counter() < end:
            with self.lock:
                if len(self.done) >= n_done:
                    return
            time.sleep(0.01)
        raise RuntimeError(f"serve: the lead-in did not finish "
                           f"{n_done} requests in {timeout}s")

    def finish(self):
        self.stop.set()
        for t in self.threads:
            t.join(REQUEST_TIMEOUT_S + 10)
        if any(t.is_alive() for t in self.threads):
            raise RuntimeError("serve: a client did not stop")


def measure(records, t0, t1, timeout_ms):
    """The window's end-to-end numbers from the requests' records."""
    ended = [r for r in records if t0 <= r.t_done <= t1]
    tokens = sum(1 for r in records for t in r.t_tokens if t0 <= t <= t1)
    ttft, tpot = [], []
    for r in ended:
        if r.failed or len(r.t_tokens) < 2:
            ttft.append(timeout_ms)     # a failed request misses any limit
            tpot.append(timeout_ms)
            continue
        ttft.append(1e3 * (r.t_tokens[0] - r.t_submit))
        tpot.append(1e3 * (r.t_tokens[-1] - r.t_tokens[0])
                    / (len(r.t_tokens) - 1))
    return ended, tokens, ttft, tpot


def sample_for_check(ended, n, seed):
    """``n`` of the requests the window finished, drawn from the seed,
    the longest among them."""
    good = [r for r in ended if not r.failed]
    if not good:
        return []
    longest = max(range(len(good)),
                  key=lambda i: len(good[i].prompt) + len(good[i].tokens))
    rest = [i for i in range(len(good)) if i != longest]
    rng = traffic.rng_for(seed, "check_sample")
    picks = [longest] + list(rng.permutation(rest)[:n - 1])
    return [good[i] for i in picks]


def token_gap(scorer, gaps_fn, sample):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of the sample."""
    worst, n = 0.0, 0
    for r in sample:
        g = gaps_fn(scorer(r.prompt, r.tokens), r.tokens)
        worst, n = max(worst, float(g.max())), n + len(g)
    return worst, n


def warm_up(program, tr, vocab_size, seed):
    """Every prefill bucket the mix can hit, and the decode program."""
    for prompt, n_new in traffic.warmup_requests(tr, vocab_size, seed):
        rec = serve_one(program, Record(prompt, n_new))
        if rec.failed:
            raise RuntimeError(f"serve: warm-up request failed: {rec.error}")


def drive(program, tr, vocab_size, seed, seconds, profiler=None):
    """Start the clients, let the lead-in pass, hold the window open
    for ``seconds``, let the clients finish.  Returns (records, t0, t1,
    programs compiled between t0 and the clients' end)."""
    import jax
    clients = Clients(program,
                      traffic.closed_loop_requests(tr, vocab_size, seed))
    clients.start()
    clients.wait_for(tr["lead_requests"], 600)
    programs = program.programs()
    if profiler:
        profiler.start()
    t0 = time.perf_counter()
    if profiler:
        with jax.profiler.TraceAnnotation("pb.window"):
            time.sleep(seconds)
        t1 = time.perf_counter()
        profiler.stop()
    else:
        time.sleep(seconds)
        t1 = time.perf_counter()
    clients.finish()
    return clients.done, t0, t1, program.programs() - programs


def run(ctx):
    cell, cfg, dims, args = ctx.cell, ctx.cfg, ctx.dims, ctx.args
    tr = ctx.mix
    ref = harness.module("reference", cfg["reference"])
    adapter = harness.module("adapters", cfg["adapter"])
    V = dims["vocab_size"]
    program = adapter.build(cfg, dims, tr["serving"],
                            ref.init_weights(dims, args.seed))
    t_built = time.perf_counter()
    warm_up(program, tr, V, args.seed)
    ctx.note(f"set-up: build {t_built - ctx.t_start:.1f}s, warm-up "
             f"{time.perf_counter() - t_built:.1f}s")
    profiler = harness.Profiler(cell["name"]) if args.trace else None
    seconds = min(args.seconds, cell["trace_seconds"]) if profiler \
        else args.seconds
    records, t0, t1, compiled_in_window = drive(
        program, tr, V, args.seed, seconds, profiler)
    setup_s = t0 - ctx.t_start
    memory_peak = harness.memory_peak(ctx.devices)
    program.free()
    del program

    window_s = t1 - t0
    ended, tokens, ttft, tpot = measure(records, t0, t1,
                                        1e3 * REQUEST_TIMEOUT_S)
    if not ended:
        raise RuntimeError("serve: no request ended in the window")
    metrics = {"serve_tokens_per_s": tokens / window_s,
               "tpot_p95_ms": harness.percentile(tpot, 95),
               "setup_s": setup_s}
    facts = {"window_s": window_s, "t0": t0, "t1": t1, "records": records,
             "traffic": tr, "tokens": tokens, "requests": len(ended),
             "ttft_p95_ms": harness.percentile(ttft, 95),
             "ttft_p50_ms": harness.percentile(ttft, 50),
             "tpot_p50_ms": harness.percentile(tpot, 50)}

    t_ref = time.perf_counter()
    sample = sample_for_check(ended, tr["check_requests"], args.seed)
    scorer = ref.make_scorer(dims, args.seed,
                             tr["prompt_len"]["hi"] + tr["new_tokens"]["hi"],
                             tr["new_tokens"]["hi"])
    gap, n_tokens = token_gap(scorer, ref.gaps, sample)
    failed = sum(1 for r in ended if r.failed)
    numbers = {"token_gap": gap if sample else float("nan"),
               "failed_requests": float(failed),
               "compiled_in_window": float(compiled_in_window)}
    ctx.note(f"window: {len(ended)} requests ended, {tokens} tokens; "
             f"checked {len(sample)} requests, {n_tokens} served tokens; "
             f"reference: {time.perf_counter() - t_ref:.1f}s after the window")
    return {"metrics": metrics, "facts": facts, "numbers": numbers,
            "attempted": len(ended), "failed": failed,
            "memory_peak": memory_peak, "profiler": profiler}
