"""Traffic kind ``serve_open_loop``: requests arrive on a schedule, whether or
not earlier ones have finished, and ``ServingEngine`` serves them.

The traffic file gives the arrival process and length distributions (read by
``traffic_gen.open_loop_requests``) and the server's options. The loop below
is ``ServingEngine.run``'s own (submit everything, admit by ``arrival_time``,
``step()`` until nothing is pending) with one difference: it gives up
``DRAIN_LIMIT_S`` after the window. A request unfinished by then is failed,
the run is not ``correct``, and the request stays in the tails: its time to
first token counts as the time it had waited when the loop gave up. Only
requests due inside the window are submitted, so after it the loop only
drains.

Every time is the engine's: ``RequestResult.arrival_time`` (when the request
was due), ``admitted_time``, ``first_token_time``, ``token_times`` (stamped
at token commit, after the fenced fetch).

``correct``: a served token's reference logit may sit at most the mix's
``check.logit_tol`` below the reference's best for that position (greedy
decoding through the cache picks the argmax of the served logits), and by at
most ``check.mean_gap_tol`` on average over the replayed positions: one far
token and many near ones are different faults. The mix's ``check.why`` says
where its numbers came from. Of a configuration this file
reads ``family`` alone: sizes come from the family's ``shapes()``, and the
reference takes its own hyper-parameters from the configuration's dict.
"""
from __future__ import annotations

import time
from typing import Mapping

import numpy as np

from benchmarks import harness, stats, traffic_gen

DRAIN_LIMIT_S = 10.0
REPLAYED_REQUESTS = 16   # about 1,650 positions: the mean gap over 4 requests
#                          spread twice as widely from seed to seed (PERF.md)


def _rehearsal(server: Mapping, arrivals: Mapping):
    """Sandbox sizes for ``rehearse=True``: tiny lengths, few slots."""
    short = {"dist": "uniform", "min": 4, "max": 12}
    arrivals = dict(arrivals, rate=20.0, prompt=short, output=short,
                    max_total=64)
    server = dict(server, num_slots=4, max_len=64, buckets=[16, 32],
                  dtype="fp32", trace_seconds=0.3)
    return server, arrivals


def run(cell: Mapping, *, seed: int, seconds: float, trace: bool,
        clock0: float, rehearse: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    traffic, cfg = cell["traffic_file"], cell["config_file"]
    family = harness.module("families", cfg["family"])
    reference = harness.module("reference", cfg["family"])
    server, arrivals = traffic["server"], traffic["arrivals"]
    logit_tol = traffic["check"]["logit_tol"]
    mean_gap_tol = traffic["check"]["mean_gap_tol"]
    if rehearse:
        cfg = family.tiny(cfg)
        server, arrivals = _rehearsal(server, arrivals)
    shapes = family.shapes(cfg)
    guard = harness.device_guard(cell["chips"], rehearse=rehearse)
    if cell["chips"] != 1:
        raise ValueError("serve_open_loop drives one chip")
    t_import = time.perf_counter()

    import deepspeed_tpu
    from deepspeed_tpu.serving import Request, ServingEngine
    from deepspeed_tpu.telemetry.registry import MetricsRegistry
    from deepspeed_tpu.telemetry.spans import SpanTracer
    from deepspeed_tpu.utils import groups

    groups.reset()
    model = family.build_model(cfg, traffic.get("model_options", {}))
    engine = deepspeed_tpu.init_inference(
        model, dtype=server["dtype"], max_out_tokens=server["max_len"],
        seed=traffic_gen.fold_seed(seed))
    registry = MetricsRegistry()
    tracer = SpanTracer() if trace else None
    srv = ServingEngine(engine, num_slots=server["num_slots"],
                        max_len=server["max_len"],
                        buckets=tuple(server["buckets"]), telemetry=registry,
                        tracer=tracer, tenants=False)
    srv.warmup()
    jax.block_until_ready(srv.cache.carry())
    t_warm = time.perf_counter()

    planned = traffic_gen.open_loop_requests(
        arrivals, seed=seed, seconds=seconds, vocab_size=shapes["vocab"])
    for p in planned:
        srv.submit(Request(rid=p.rid, prompt=p.prompt,
                           max_new_tokens=p.max_new_tokens,
                           arrival_time=p.arrival_time))
    programs_before = srv.program_cache_sizes()

    # ---- the window and its drain
    trace_s = server["trace_seconds"]
    results, reduced, trace_span = [], None, None
    with harness.profile_if(trace) as prof:
        t0 = time.monotonic()
        srv._run_t0 = t0     # as run() does: token stamps read a fresh clock
        setup_s = time.perf_counter() - clock0
        while srv.pending:
            now = time.monotonic() - t0
            if now > seconds + DRAIN_LIMIT_S:
                break
            if prof is not None:
                # profiler up a second early; the window annotation opens at
                # an iteration boundary, the last trace_s of the window
                if not prof.on and now >= seconds - trace_s - 1.0:
                    prof.start()
                elif (prof.on and trace_span is None
                        and now >= seconds - trace_s):
                    prof.open_window()
                    trace_span = [time.monotonic() - t0, None]
                elif (trace_span and trace_span[1] is None
                        and now >= seconds):
                    prof.close_window()
                    trace_span[1] = time.monotonic() - t0
            if not any(s is not None for s in srv._slots):
                nxt = srv.scheduler.next_arrival()
                if nxt is not None and nxt > now:
                    time.sleep(min(nxt - now, 0.005))
                    continue
            results.extend(srv.step(now))
        gave_up = time.monotonic() - t0
        drained_s = gave_up - seconds
        if prof is not None:
            if trace_span and trace_span[1] is None:
                prof.close_window()
                trace_span[1] = time.monotonic() - t0
            reduced = prof.reduce()
    programs_after = srv.program_cache_sizes()
    # jit caches only grow: new entries of old programs and whole new programs
    compiles = sum(programs_after.values()) - sum(programs_before.values())

    by_rid = {r.rid: r for r in results}
    done = [by_rid[p.rid] for p in planned if p.rid in by_rid
            and len(by_rid[p.rid].tokens) == p.max_new_tokens]
    failed = len(planned) - len(done)
    # The tails are over every request due in the window. One that the engine
    # never returned (still queued or running when the drain gave up) counts
    # as ``gave_up - arrival``, at least DRAIN_LIMIT_S, so it sits at the top
    # of the tail and the run is not ``correct``; its token stamps are lost
    # with it, so it adds no gaps and no tokens to the rate.
    ttft = stats.times_to_first_token(
        {p.rid: p.arrival_time for p in planned},
        {r.rid: r.first_token_time for r in results if r.token_times},
        gave_up)
    gaps = stats.inter_token_gaps(r.token_times for r in results)
    committed = stats.count_in_window(
        (r.token_times for r in results), 0.0, seconds)

    # ---- correct: replay served tokens, teacher-forced, through the plain
    # reference's full forward on the engine's weights (after the window)
    t_chk = time.perf_counter()
    positions = shapes["positions"] if not rehearse else server["max_len"]
    ref_fn = jax.jit(lambda p, x: reference.forward_logits(p, x, cfg))
    worst, gap_sum, exact, checked, replayed = 0.0, 0.0, 0, 0, []
    plan = {p.rid: p for p in planned}
    for r in done[:REPLAYED_REQUESTS]:
        prompt = plan[r.rid].prompt
        seq = np.zeros((1, positions), np.int32)
        seq[0, :len(prompt) + len(r.tokens)] = prompt + list(r.tokens)
        logits = ref_fn(engine.params, jnp.asarray(seq))[0]
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(r.tokens)]
        gap = rows.max(-1) - jnp.take_along_axis(
            rows, jnp.asarray(r.tokens)[:, None], -1)[:, 0]
        worst = max(worst, float(gap.max())) if bool(
            jnp.all(jnp.isfinite(rows))) else float("inf")
        gap_sum += float(gap.sum())
        exact += int((gap == 0).sum())
        checked += len(r.tokens)
        replayed.append([len(r.tokens), float(gap.max()), float(gap.sum())])
    mean_gap = gap_sum / checked if checked else float("inf")
    check_s = time.perf_counter() - t_chk

    e2e = {"setup_s": setup_s, "serve_tokens_per_s": committed / seconds,
           "ttft_p95_ms": stats.percentile(ttft, 95.0) * 1e3}
    if gaps:
        e2e["itl_p95_ms"] = stats.percentile(gaps, 95.0) * 1e3
    counters = registry.snapshot()["counters"]
    requests = [{"rid": r.rid, "prompt_len": r.prompt_len,
                 "arrival": r.arrival_time, "admitted": r.admitted_time,
                 "first_token": r.first_token_time,
                 "token_times": list(r.token_times)} for r in done]
    spans = [] if tracer is None else [
        {"name": s.name, "start": s.start, "end": s.end} for s in tracer.spans]
    return {
        "correct": (failed == 0 and checked > 0 and worst <= logit_tol
                    and mean_gap <= mean_gap_tol),
        "attempted": len(planned), "failed": failed,
        "end_to_end": e2e,
        "device": dict(guard["device"], memory_peak_bytes=harness.
                       memory_peak_bytes(guard["devices"][:1])),
        "notes": {
            "requests": len(planned), "finished": len(done),
            "offered_rate_per_s": arrivals["rate"],
            "output_tokens_planned": sum(p.max_new_tokens for p in planned),
            "tokens_in_window": committed, "drain_s": drained_s,
            # beside the judged 95th percentile, two steadier candidates
            # from the same list, as evidence for a later choice (PERF.md)
            "ttft_ms": {"p50": stats.median(ttft) * 1e3,
                        "mean_p90_p99":
                            stats.mean_between(ttft, 90.0, 99.0) * 1e3,
                        "share_within_100ms":
                            stats.share_within(ttft, 0.100),
                        "n": len(ttft)},
            "itl_ms": {"p50": stats.median(gaps) * 1e3 if gaps else None,
                       "n": len(gaps)},
            "worst_logit_gap": worst, "logit_tol": logit_tol,
            "mean_logit_gap": mean_gap, "mean_gap_tol": mean_gap_tol,
            "replayed": replayed,   # per request: tokens, largest gap, sum
            "exact_argmax": [exact, checked], "reference_check_s": check_s,
            "programs": programs_after,
            "setup_parts_s": {"import_and_guard": t_import - clock0,
                              "build_and_warmup": t_warm - t_import,
                              "traffic_and_submit":
                                  setup_s - (t_warm - clock0)},
        },
        "observations": {
            # every counter the program kept, under its registry name, and
            # the short names of the first metric files beside them
            "counters": {
                **counters,
                "compiles_in_window": compiles,
                "decode_steps": counters.get("serving/decode_steps", 0),
                "slot_iterations_active":
                    counters.get("serving/slot_iterations_active", 0),
                "num_slots": server["num_slots"],
            },
            "requests": requests, "spans": spans, "trace": reduced,
            "trace_span": trace_span, "peak": guard["peak"],
            "shapes": shapes,
        },
    }
