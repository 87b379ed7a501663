"""One run of one cell: set-up, the checked first steps, the timed window,
the traced stretch, and after all of that the reference.

The loop is the same for every cell. Each step takes the NEXT host batch
(`put`: a `jax.device_put`, so the copy is on the clock) and step i+1 is
dispatched before the loop waits for the loss of step i, as a training
loop that logs its loss does. Completion times are taken as each loss
becomes ready; the window ends with `block_until_ready` on the last
state.
"""
from __future__ import annotations

import functools
import gc
import math
import tempfile
import time

from benchmarks.harness import cells, check, data, reference_train
from benchmarks.harness import trace as tr
from benchmarks.harness.compile_log import CompileLog

CHECK_STEPS = 3        # the first steps, which the reference follows
TRACE_STEPS = 10       # the traced stretch, where the mix names no other
SPANS = ("feed", "dispatch", "wait")


def log(msg: str) -> None:
    import sys
    print(msg, file=sys.stderr, flush=True)


def window(step, state, put, pool, first: int, seconds: float,
           max_steps: int | None = None):
    """Drive `step` for `seconds` (or `max_steps`). Returns the state and
    the window's record: its start and end, when each step completed,
    what each feed took, and every loss."""
    import jax
    from jax.profiler import TraceAnnotation
    done, feed_s, losses = [], [], []
    pending = None
    i = 0
    t0 = time.perf_counter()
    while True:
        if i and (i >= max_steps if max_steps else
                  time.perf_counter() - t0 >= seconds):
            break
        with TraceAnnotation("feed"):
            tf = time.perf_counter()
            batch = put(pool[(first + i) % len(pool)])
            feed_s.append(time.perf_counter() - tf)
        with TraceAnnotation("dispatch"):
            state, loss = step(state, batch)
        i += 1
        if pending is not None:
            with TraceAnnotation("wait"):
                losses.append(float(pending))
            done.append(time.perf_counter())
        pending = loss
    with TraceAnnotation("wait"):
        losses.append(float(pending))
        done.append(time.perf_counter())
        jax.block_until_ready(state)
    t1 = time.perf_counter()
    return state, {"t0": t0, "t1": t1, "done": done, "feed_s": feed_s,
                   "losses": losses}


def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics."""
    v = sorted(values)
    pos = (len(v) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def step_intervals(done, t0: float, k: int = 1) -> list:
    """Seconds per step between completions `k` steps apart, for every
    step. `k` is the mix's `interval_steps`, fixed there so that no gain
    moves it: 1, the interval between successive completions, wherever a
    step takes a quarter of a second or more, since the host's clock is
    off by some half a millisecond."""
    times = [t0] + list(done)
    k = min(k, len(times) - 1)
    return [(times[i + k] - times[i]) / k for i in range(len(times) - k)]


def end_to_end(rec: dict, tokens_per_step: int, chips: int,
               setup_s: float, k: int = 1) -> dict:
    steps = len(rec["done"])
    intervals = step_intervals(rec["done"], rec["t0"], k)
    log(f"[window] {steps} steps in {rec['t1'] - rec['t0']:.3f} s; "
        f"step_ms_p90 over {len(intervals)} intervals of {k} step(s)")
    return {
        "setup_s": setup_s,
        "tokens_per_s_per_chip":
            tokens_per_step * steps / (rec["t1"] - rec["t0"]) / chips,
        "step_ms_p90": 1e3 * percentile(intervals, 0.9),
    }


def reference_blocks(mix: dict) -> int:
    """In how many blocks of rows the reference takes a batch (the mix's
    `reference_blocks`): only where every row is full, so that every
    block weighs the same in the batch's mean."""
    blocks = mix.get("reference_blocks", 1)
    if blocks > 1 and not (mix["lengths"]["lo"] == mix["lengths"]["hi"]
                           and mix["batch"] % blocks == 0):
        raise ValueError("reference_blocks needs rows of one length and a "
                         "batch that divides")
    return blocks


def device_info(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             peak: dict, t_start: float, wrap_step=None,
             keep: dict | None = None) -> dict:
    """The whole of a run after the look for a chip. `wrap_step` lets a
    test break the timed path underneath; `keep` receives both records
    of the first steps, leaf by leaf (`readings.py` sets limits from
    them). With `seconds` 0 there is no window: the first steps and
    their comparison alone. Returns the result line."""
    import jax
    config, mix = spec["config"], spec["mix"]
    chips = spec["cell"]["chips"]
    trace_steps = mix.get("trace_steps", TRACE_STEPS)
    adapter, reference = cells.family(config)
    compiles = CompileLog()
    stats = data.batch_stats(mix)
    pool = data.make_pool(mix, config["vocab_size"], seed)
    key = jax.random.key(seed)
    init = functools.partial(reference.init_weights, config)
    prog = adapter.build(config, mix, jax.jit(init)(key), devices)
    step = wrap_step(prog.step) if wrap_step else prog.step
    state, prog.state = prog.state, None
    log(f"[setup] built in {time.perf_counter() - t_start:.1f} s")

    # the first steps, through the window's own call and feed; the
    # reference follows them once the window has closed
    norms = jax.jit(reference_train.leaf_norms)
    change = jax.jit(lambda now, k: reference_train.delta_norms(now, init(k)))
    beta1 = config["optimizer"]["beta1"]
    got = {"losses": []}
    for i in range(CHECK_STEPS):
        t_step = time.perf_counter()
        state, loss = step(state, prog.put(pool[i]))
        got["losses"].append(float(loss))
        log(f"[setup] step {i + 1} took {time.perf_counter() - t_step:.2f} s"
            + (" (traced, lowered, compiled or read from the cache)"
               if i == 0 else ""))
        if i == 0:
            first = reference_train.flatten_norms(
                jax.device_get(norms(prog.moment1(state))))
            got["grad"] = {k: v / (1 - beta1) for k, v in first.items()}
    got["change"] = reference_train.flatten_norms(
        jax.device_get(change(prog.params(state), key)))
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {setup_s:.2f} s; compile requests {compiles.requests}, "
        f"persistent-cache hits {compiles.cache_hits}; first losses "
        f"{got['losses']}")

    before = compiles.requests
    metrics, extra, rec = {}, {}, None
    if seconds > 0:
        state, rec = window(step, state, prog.put, pool, CHECK_STEPS,
                            seconds)
        metrics = end_to_end(rec, stats["tokens"], chips, setup_s,
                             mix.get("interval_steps", 1))
    traced = None
    if trace:
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                at = CHECK_STEPS + (len(rec["done"]) if rec else 0)
                state, trec = window(step, state, prog.put, pool, at, 0.0,
                                     max_steps=trace_steps)
            finally:
                jax.profiler.stop_trace()
            traced = tr.load(tr.find_xplane(tmp), SPANS)
        rec = rec or trec
    in_window = compiles.requests - before
    losses = rec["losses"] if rec else got["losses"]
    bad = sum(1 for x in losses if not math.isfinite(x))

    device = device_info(devices)
    del state, step
    prog = None
    gc.collect()

    t_ref = time.perf_counter()
    asked, hit = compiles.requests, compiles.cache_hits
    ref = reference_train.run(reference, config, pool[:CHECK_STEPS], seed,
                              blocks=reference_blocks(mix), devices=devices)
    ok, compared = check.compare(got, ref, spec["limits"])
    if keep is not None:
        keep.update(got=got, ref=ref)
    log(f"[check] reference took {time.perf_counter() - t_ref:.1f} s "
        f"(compile requests {compiles.requests - asked}, persistent-cache "
        f"hits {compiles.cache_hits - hit}); its losses {ref['losses']}")
    compared["compiles_in_window"] = {"value": in_window, "limit": 0}
    compared["nonfinite_losses"] = {"value": bad, "limit": 0}
    correct = bool(ok and in_window == 0 and bad == 0)

    if trace:
        ctx = {"trace": traced, "window": rec, "stats": stats,
               "chips": chips, "peak": peak, "config": config, "mix": mix,
               "counts": reference.counts(config, stats),
               "trace_steps": trace_steps}
        ctx["summary"] = tr.summary(traced)
        out = {}
        for m in spec["per_layer"]:
            read, params = cells.reader(m["name"])
            value = read(ctx, params)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        for note in ctx.get("notes", ()):
            log(f"[trace] {note}")
        s = ctx["summary"]
        if s:
            device["busy_s"], device["window_s"] = s["busy_s"], s["window_s"]
            extra["breakdown"] = {"device_ops": s["device_ops"],
                                  "idle_gaps": s["idle_gaps"]}
            log(f"[trace] {s['steps']} step programs over "
                f"{s['window_s']:.3f} s, busy by device "
                f"{s['busy_by_device']}")
        metrics = out
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in metrics.items() if k in units}

    for name, c in compared.items():
        log(f"[compared] {name} {c['value']!r} limit {c['limit']!r}"
            + (f" at {c['at']}" if c.get("at") else ""))
    log(f"[done] {time.perf_counter() - t_start:.1f} s since the process "
        "started")
    return {"correct": correct, "attempted": len(losses), "failed": bad,
            "metrics": metrics, "device": device, **extra,
            "compared": compared}
