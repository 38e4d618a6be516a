"""The ``serve_hybrid`` kind: ``serve_runner``'s loop, checks and record — one
``ServingEngine`` under an open loop of seeded arrivals at the rate fixed in
the cell's file, every request timed from when it was DUE, the plain reference
over a seeded sample of the finished requests — for a configuration whose
tree is not the dense decoder's: the leaf that shows the program's weights to
be the regenerated ones is asked of the configuration's own weights module
(``weights.probe(params, cfg, seed, dtype)``), and the reference reads its
head at the served positions alone (``served_token_gaps`` of the
configuration's reference). What a seat holds beside its K/V rows is in the
record as ``state_bytes_per_slot``. A cell that names a ``handoff_sample``
(``requests``, ``decoded``, ``width``) also holds ``first_decoded_mean_logit_gap``
to a limit: the gaps of the first tokens that the DECODE steps of more, shorter
requests served — what a state lost between prefill and decode moves and the
mean over whole answers does not.

(Two runners with one loop: ROADMAP Reach B6 folds them into one.)
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import common, traffic
from .serve_runner import Step, _mean_live_tokens, gap_stats  # noqa: F401


def run(cell, seed, seconds, trace, t_start, say):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import ServingEngine
    from accelerate_tpu.models import CausalLM

    spec, cfg = cell["spec"], cell["config"]
    reference, weights = common.modules_of(cfg)
    eng_spec, mix, limits = spec["engine"], spec["traffic"], spec["limits"]
    cache_dir, monitor = common.activate_cache()
    compile_before = monitor.snapshot()
    dtype = jnp.dtype(spec["weight_dtype"])
    say(f"cell {cell['name']}: {cfg['num_hidden_layers']} layers, "
        f"{eng_spec['max_slots']} slots x {eng_spec['max_seq_len']} tokens, "
        f"{mix['rate_per_s']} requests/s offered; compile cache {cache_dir}")

    # ---- the system under test ------------------------------------------ #
    device = jax.devices()[0]
    model = CausalLM(common.program_config(
        cfg, max_seq_len=eng_spec["max_seq_len"], dtype=spec["weight_dtype"]))
    params = weights.make_tree(cfg, seed, dtype,
                               out_shardings=jax.sharding.SingleDeviceSharding(device))
    engine = ServingEngine(model, params, max_slots=eng_spec["max_slots"],
                           block_size=eng_spec["block_size"],
                           now=time.perf_counter, span_history=4096)
    requests = traffic.serve_requests(mix, seed, seconds, cfg["vocab_size"])

    tokens_of: dict = {}   # request id -> [token]
    times_of: dict = {}    # request id -> [host time of each token]
    steps: list[Step] = []
    clock = {"t0": time.perf_counter()}
    tracer = common.Tracer(cell, trace)

    def now():
        return time.perf_counter() - clock["t0"]

    def submit(req):
        req["id"] = engine.add_request(req["prompt"],
                                       max_new_tokens=req["max_new_tokens"])
        req["sent_s"] = now()
        tokens_of[req["id"]], times_of[req["id"]] = [], []

    def step():
        before, t = engine.prefill_bucket_tokens_total, now()
        with tracer.span("engine_step"):
            events = engine.step()
        t1 = now()
        firsts = 0
        for ev in events:
            firsts += not tokens_of[ev.request_id]
            tokens_of[ev.request_id].append(int(ev.token))
            times_of[ev.request_id].append(t1)
        # a step ingested a prompt if the engine's prefill counter moved
        ingested = firsts if engine.prefill_bucket_tokens_total > before else 0
        seated = sum(1 for s in engine.scheduler.slots if s.busy)
        steps.append(Step(t, t1 - t, ingested, seated, len(events)))

    # ---- warm-up: every prefill width of this mix, and decode ----------- #
    rng = np.random.default_rng([seed, 0xA11])
    for width in traffic.prompt_buckets(mix):
        # width - 2 tokens land in the width-wide prefill program and, with
        # the two new tokens, stay inside max_seq_len at the widest
        warm = {"prompt": rng.integers(0, cfg["vocab_size"], width - 2).astype(np.int32),
                "max_new_tokens": 2}
        submit(warm)
    while engine.has_work:
        step()
    pre = [r for r in requests if r["due_s"] is None]
    timed = [r for r in requests if r["due_s"] is not None]
    for r in pre:  # seat every slot before the window opens
        submit(r)
    seated_first = 0
    while pre and seated_first < len(pre):
        step()
        seated_first = sum(1 for r in pre if tokens_of[r["id"]])
    setup_compile = monitor.delta(compile_before)

    # ---- the measured window --------------------------------------------- #
    in_window = monitor.snapshot()
    clock["t0"] = time.perf_counter()
    setup_s = clock["t0"] - t_start
    # token times of the pre-seated requests so far are before the window
    for r in pre:
        times_of[r["id"]] = [-1.0] * len(times_of[r["id"]])
    steps.clear()
    nxt, late = 0, []
    while now() < seconds:
        tracer.tick(now(), seconds)
        while nxt < len(timed) and timed[nxt]["due_s"] <= now():
            submit(timed[nxt])
            late.append(timed[nxt]["sent_s"] - timed[nxt]["due_s"])
            nxt += 1
        if engine.has_work:
            step()
        else:
            wake = min(seconds, timed[nxt]["due_s"] if nxt < len(timed) else seconds)
            with tracer.span("arrival_wait"):
                time.sleep(max(0.0, min(wake - now(), 0.05)))
    window_steps = len(steps)
    for r in timed[nxt:]:  # due before the close, not yet sent
        submit(r)
        late.append(r["sent_s"] - r["due_s"])
    tracer.stop()  # stalls the host for seconds: after the close, then the drain
    drain_limit = seconds + float(spec["drain_limit_s"])
    while engine.has_work and now() < drain_limit:
        step()
    drained_s = now()
    window_compile = monitor.delta(in_window)

    # ---- what the window produced ---------------------------------------- #
    chk = common.Checks(say)
    peak = common.memory_peak_bytes_of(jax)
    counts, pool = engine.trace_counts(), engine.pool.stats()
    everyone = pre + timed
    finished = [r for r in everyone
                if len(tokens_of[r["id"]]) == r["max_new_tokens"]
                and engine.result(r["id"]) == tokens_of[r["id"]]]
    failed = len(everyone) - len(finished)
    in_range = all(0 <= t < cfg["vocab_size"]
                   for r in finished for t in tokens_of[r["id"]])
    queue_ms = []
    for span in list(engine.span_log.closed):
        if span.admit_t is not None:
            queue_ms.append((span.admit_t - span.submit_t) * 1e3)
    chk.exact("requests_not_finished_by_drain_limit", failed, 0)
    chk.exact("served_tokens_in_range", in_range, True)
    chk.exact("pool_blocks_allocated_after_drain",
              pool["allocated"] if not engine.has_work else -1, 0)
    chk.exact("decode_traced", counts["decode"], 1)
    chk.exact("compiles_in_window", common.compiles_in(window_compile), 0)

    # seeded sample of finished requests, the longest among them
    pick_rng = np.random.default_rng([seed, 0x5A3])
    by_len = sorted(finished, key=lambda r: -(len(r["prompt"]) + r["max_new_tokens"]))
    sample = by_len[:1] + [by_len[i] for i in pick_rng.permutation(
        np.arange(1, len(by_len)))[:int(spec["reference_sample"]) - 1]]
    seqs = [list(map(int, r["prompt"])) + tokens_of[r["id"]] for r in sample]
    plens = [len(r["prompt"]) for r in sample]
    # the state a prefill hands to the decode steps bears on the few tokens
    # behind a prompt and on no other, so eight requests say little of it:
    # ``handoff_sample`` takes MORE finished requests, each cut behind the
    # first ``decoded`` tokens its decode steps served, through a narrower
    # pass of the same reference (prompts that fit ``width`` with them)
    hand = spec.get("handoff_sample") or {"requests": 0, "decoded": 0, "width": 0}
    keep = 1 + int(hand["decoded"])  # the prefill's own token, then the decoded
    fits = [r for r in finished if r["max_new_tokens"] >= keep
            and len(r["prompt"]) + keep <= hand["width"]]
    first = [fits[i] for i in np.random.default_rng([seed, 0x4A0]).permutation(
        len(fits))[:int(hand["requests"])]]
    first_seqs = [list(map(int, r["prompt"])) + tokens_of[r["id"]][:keep]
                  for r in first]
    # the reference regenerates the weights from the seed: show on the
    # configuration's own probed leaves that they are the program's
    chk.limit("program_weights_vs_regenerated_normalized_max_error",
              weights.probe(params, cfg, seed, dtype), 1e-2)

    # ---- free the engine, then the reference ----------------------------- #
    kv_bytes_per_token = float(engine.kv_bytes_per_token)
    state_bytes_per_slot = float(engine.state_bytes_per_slot)
    del engine, params, model
    gc.collect()
    t_ref = time.perf_counter()
    # always the same shapes (a row of max_seq_len, a row of the hand-off's
    # width), so that the reference's programs are in the compile cache after
    # the cell's first run
    both = reference.served_token_gaps(
        cfg, seed, seqs + first_seqs, plens + [len(r["prompt"]) for r in first],
        dtype, rows=int(spec["reference_rows_per_block"]),
        width=[eng_spec["max_seq_len"]] * len(seqs) + [hand["width"]] * len(first))
    ref_s = time.perf_counter() - t_ref
    gaps = {part: rows[:len(seqs)] for part, rows in both.items()}
    stats = gap_stats(gaps["gap"])
    # a request's first served token is its prefill's own: the state is read
    # from its second on
    first_gaps = [np.asarray(g, np.float64)[1:] for g in both["gap"][len(seqs):]]
    stats["first_decoded_mean_logit_gap"] = (
        float(np.mean(first_gaps)) if first_gaps else float("nan"))
    for name, limit in limits.items():  # the cell's file says which numbers judge
        chk.limit(name, stats[name], limit)
    say(f"reference: {len(seqs)} requests, {stats['n']} served tokens, longest "
        f"{max(map(len, seqs)) if seqs else 0} positions, took {ref_s:.1f}s "
        f"(outside setup_s); {stats['off_best']} tokens off the reference's best; "
        + ", ".join(f"{k} {v:.4g}" for k, v in stats.items()
                    if k not in limits and k not in ("n", "off_best")))
    say(f"hand-off: the first {keep - 1} decoded tokens of {len(first)} of the "
        f"{len(fits)} finished requests that fit {hand['width']} positions; "
        "mean gap a request "
        + " ".join(f"{g.mean():.4f}" for g in first_gaps))

    # ---- the run record the readers read ---------------------------------- #
    ttft_ms, itl_ms, worst_ms = [], [], (drained_s + 1.0) * 1e3
    for r in timed:
        ts = times_of[r["id"]]
        ttft_ms.append((ts[0] - r["due_s"]) * 1e3 if ts and r in finished
                       else worst_ms)
        itl_ms += list(np.diff(ts) * 1e3)
    tokens_in_window = sum(
        1 for r in everyone for t in times_of[r["id"]] if 0.0 <= t <= seconds)
    win = steps[:window_steps]
    decode_only = [s for s in win if not s.prompts and s.tokens]
    with_prefill = [s for s in win if s.prompts]
    say(f"window: {len(timed)} requests due, {len(finished)} of {len(everyone)} "
        f"finished, {tokens_in_window} tokens in {seconds}s; {len(win)} steps "
        f"({len(with_prefill)} with prefill); TTFT samples {len(ttft_ms)}, gap "
        f"samples {len(itl_ms)}; generator late by median "
        f"{np.median(late) * 1e3 if late else 0:.1f} ms, worst "
        f"{max(late) * 1e3 if late else 0:.1f} ms; drained at {drained_s:.1f}s")
    say(f"setup_s {setup_s:.2f} (compile {setup_compile['compile_time_s']:.1f}s "
        f"inside it); peak {peak / 2**30:.2f} GiB")
    if ttft_ms:
        say(f"(judges nothing: no bound holds it) TTFT from due p50 "
            f"{np.median(ttft_ms):.0f} ms p90 {np.percentile(ttft_ms, 90):.0f} ms; "
            f"queue (admit - submit) p50 {np.median(queue_ms) if queue_ms else 0:.2f} ms; "
            f"gap p50 {np.median(itl_ms):.1f} ms p95 {np.percentile(itl_ms, 95):.1f} ms")
    record = {
        "correct": chk.ok, "attempted": len(timed),
        "failed": sum(1 for r in timed if r not in finished),
        "memory_peak_bytes": peak,
        "setup_s": setup_s, "window_s": float(seconds), "chips": 1,
        "tokens_in_window": tokens_in_window,
        "ttft_ms": ttft_ms, "itl_ms": itl_ms, "queue_ms": queue_ms,
        "decode_step_ms": [s.took_s * 1e3 for s in decode_only],
        "prefill_step_s": [s.took_s for s in with_prefill],
        "prefill_call_ms": [s.took_s * 1e3 / s.prompts for s in with_prefill],
        "seated_share": [s.seated / eng_spec["max_slots"] for s in win],
        "mean_seated": (float(np.mean([s.seated for s in decode_only]))
                        if decode_only else 0.0),
        "mean_live_tokens": _mean_live_tokens(everyone, times_of, seconds),
        "kv_bytes_per_token": kv_bytes_per_token,
        "state_bytes_per_slot": state_bytes_per_slot,
        "cold_compile_s": float(setup_compile["compile_time_s"]),
        "reference_s": ref_s,
        "checks": chk.rows, "reference_sample": (seqs, plens),
        "gap_stats": stats, "gaps": gaps, "handoff_gaps": first_gaps,
        "backlog_at_close": len(timed) - sum(
            1 for r in timed if times_of[r["id"]]
            and len(times_of[r["id"]]) == r["max_new_tokens"]
            and times_of[r["id"]][-1] <= seconds),
        "drained_s": drained_s,
    }
    return record, tracer.result()
