"""What the serve drivers share: the model and engine from a configuration,
warm-up, the timed window over ``ServeEngine.step``, and the check of what
the window served against the plain reference.

The engine runs in-flight mode (prompt chunks and the batched decode in one
step), greedy, with the weights of ``references/bitnet.py`` made from the
seed.  Every token is stamped with the host time at the end of the step
that produced it; the engine syncs on its sampled tokens inside each step.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.references import bitnet

STEP_SPAN = "bench.step"
WAIT_SPAN = "bench.wait"


def model_sizes(config: dict) -> dict:
    """The numbers the reference and the work counts read."""
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rms_norm_eps", "rope_theta", "quant_eps")
    return {k: config[k] for k in keys}


def model_config(config: dict):
    """The program's ModelConfig: its registry entry, with every size as
    the configuration file states it."""
    from repro.configs import get_config

    return get_config(config["registry"]).replace(
        layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=config["rope_theta"], dtype=config["torch_dtype"],
        tie_embeddings=config["tie_word_embeddings"],
        quantization=config["quantization"])


def program_params(w: dict) -> dict:
    """The reference's weights in the program's parameter layout."""
    lay = w["layers"]
    return {"embed": {"tokens": w["embed"]}, "ln_f": w["ln_f"],
            "blocks": {"ln1": lay["ln1"], "ln2": lay["ln2"],
                       "attn": {k: lay[k] for k in ("wq", "wk", "wv", "wo")},
                       "mlp": {k: lay[k] for k in ("w1", "w2", "w3")}}}


def build_engine(config: dict, seed: int):
    """Weights from the seed in one jitted call, then the engine."""
    from repro.models import build_model
    from repro.serve import ServeEngine

    api = build_model(model_config(config))
    params = program_params(bitnet.served_weights(
        model_sizes(config), seed, jnp.dtype(config["torch_dtype"])))
    want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
        raise ValueError(f"the program's parameter layout changed: {want}")
    jax.block_until_ready(params)
    e = config["engine"]
    engine = ServeEngine(api, params, max_slots=e["max_slots"],
                         max_seq=e["max_seq"],
                         prefill_chunk_tokens=e["prefill_chunk_tokens"],
                         greedy=True)
    return engine


def chunk_lengths(config: dict, mix: dict) -> List[int]:
    """Every chunk length the mix can make: multiples of the prompt quantum
    up to the chunk budget (a budget shared by two prompts splits on the
    quantum, since every prompt is a multiple of it)."""
    budget = config["engine"]["prefill_chunk_tokens"]
    q = mix["prompt"]["quantum"]
    top = min(budget, mix["prompt"]["max"])
    return list(range(q, top + 1, q))


def warm_up(engine, config: dict, mix: dict) -> None:
    """Run every shape the window uses: each chunk length (one prompt at a
    time, so no two share the chunk budget), the decode at every slot,
    sampling, the slot write."""
    rng = traffic.rng(0, 9)
    for n in chunk_lengths(config, mix):
        engine.submit(rng.integers(1, config["vocab_size"], n)
                      .astype(np.int32), max_new_tokens=3)
        engine.run_until_done()
    engine.finished.clear()
    engine.decode_batch_sizes.clear()
    engine.step_log.clear()


@dataclasses.dataclass
class Served:
    """One request of the mix as the window saw it."""

    arrival_s: float
    submit_s: float
    req: object                     # the engine's Request
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    served: List[Served]
    steps: List[dict]
    seconds: float
    traced_steps: List[dict]
    late_s: List[float]
    t0: float                       # the window's open, on perf_counter


def window_seconds(ctx) -> float:
    """The window's length: ``--seconds``, or in a traced run the mix's
    lead-in and traced span."""
    if ctx.trace:
        t = ctx.mix["trace"]
        return min(t["lead_s"], ctx.seconds) + t["span_s"]
    return ctx.seconds


def run_window(ctx, engine, requests: List[traffic.Request]) -> Window:
    """Release ``requests`` at their arrival times and step the engine until
    the window closes.  In a traced run the profiler covers the mix's
    ``trace`` span of the window, and the window ends with it."""
    events: List[dict] = []
    engine.step_observers.append(events.append)
    seconds = window_seconds(ctx)
    lead = min(ctx.mix["trace"]["lead_s"], ctx.seconds) if ctx.trace else 0
    pending = sorted(requests, key=lambda r: r.arrival_s)
    nxt = 0
    live: List[Served] = []
    served: List[Served] = []
    steps: List[dict] = []
    traced: List[dict] = []
    tracing = False
    t0 = ctx.open_window()
    while True:
        now = time.perf_counter() - t0
        if ctx.trace and not tracing and now >= lead:
            ctx.start_trace()
            tracing = True
        while nxt < len(pending) and pending[nxt].arrival_s <= now:
            r = pending[nxt]
            nxt += 1
            s = Served(r.arrival_s, now, engine.submit(
                r.prompt, max_new_tokens=r.max_new_tokens))
            live.append(s)
            served.append(s)
        if now >= seconds:
            break
        if live:
            step = _step(engine, events, live, t0)
            steps.append(step)
            if tracing:
                traced.append(step)
            live = [s for s in live if not s.req.done]
        else:
            due = pending[nxt].arrival_s if nxt < len(pending) else seconds
            with jax.profiler.TraceAnnotation(WAIT_SPAN):
                time.sleep(min(due - now, 0.01))
    if tracing:
        ctx.stop_trace()
    ctx.close_window()
    engine.step_observers.remove(events.append)
    return Window(served, steps, seconds, traced,
                  [s.submit_s - s.arrival_s for s in served], t0)


def _step(engine, events: List[dict], live: List[Served], t0: float) -> dict:
    a = time.perf_counter() - t0
    with jax.profiler.TraceAnnotation(STEP_SPAN):
        engine.step()
    b = time.perf_counter() - t0
    for s in live:
        while len(s.times) < len(s.req.output):
            s.times.append(b)
    ev = events[-1] if events else {}
    events.clear()
    return {"t0": a, "t1": b,
            "chunks": [(c["tokens"], c["pos0"], c["last"])
                       for c in ev.get("chunks", [])],
            "positions": list(ev.get("positions", []))}


def drain_first_tokens(engine, window: Window, limit_s: float = 60.0) -> None:
    """Step on, taking no new requests, until every request that arrived in
    the window has its first token (it counts from its arrival)."""
    t_end = time.perf_counter() + limit_s
    waiting = [s for s in window.served if not s.times and not s.req.done]
    live = [s for s in window.served if not s.req.done]
    while waiting and time.perf_counter() < t_end:
        _step(engine, [], live, window.t0)
        live = [s for s in live if not s.req.done]
        waiting = [s for s in waiting if not s.times]


def end_to_end(window: Window) -> dict:
    """Tails and rates over every request of the window."""
    sec = window.seconds
    tokens = sum(1 for s in window.served for t in s.times if t <= sec)
    gaps = [b - a for s in window.served
            for a, b in zip(s.times, s.times[1:]) if b <= sec]
    ttft = [s.times[0] - s.arrival_s for s in window.served if s.times]
    out = {"output_tokens_per_s": tokens / sec}
    if gaps:
        out["itl_p95_ms"] = traffic.percentile(gaps, 95) * 1e3
    if ttft:
        out["ttft_p90_ms"] = traffic.percentile(ttft, 90) * 1e3
    return out


def check_sample(window: Window, seed: int, n: int) -> List[Served]:
    """Requests the window finished: the one with most served tokens, and
    ``n - 1`` more drawn from the seed."""
    done = [s for s in window.served
            if s.req.done and not s.req.truncated and not s.req.refused]
    if not done:
        return []
    done.sort(key=lambda s: (-len(s.req.output), s.req.uid))
    rest = done[1:]
    pick = traffic.rng(seed, 3).permutation(len(rest))[:n - 1]
    return [done[0]] + [rest[i] for i in sorted(pick)]


def sequences(sample: List[Served], length: int):
    """Tokens [n, length] (prompt, then every served token but the last),
    the served tokens aligned to the positions that predicted them, and a
    mask of those positions."""
    n = len(sample)
    tokens = np.zeros((n, length), np.int32)
    targets = np.zeros((n, length), np.int32)
    mask = np.zeros((n, length), bool)
    for i, s in enumerate(sample):
        p, out = s.req.prompt, np.asarray(s.req.output, np.int32)
        seq = np.concatenate([p, out[:-1]])
        tokens[i, :len(seq)] = seq
        targets[i, len(p) - 1:len(p) - 1 + len(out)] = out
        mask[i, len(p) - 1:len(p) - 1 + len(out)] = True
    return tokens, targets, mask


def free(engine) -> None:
    """Drop the engine's device state before the reference runs."""
    engine.cache = None
    engine.params = None
    for slot in engine.slots:
        slot.staging = None
    gc.collect()


def logit_gap(config: dict, seed: int, sample: List[Served]
              ) -> Optional[dict]:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of the sample."""
    if not sample:
        return None
    m = model_sizes(config)
    tokens, targets, mask = sequences(sample, config["engine"]["max_seq"])
    (gap,) = bitnet.token_gaps(m, seed, tokens, [targets],
                               config["torch_dtype"])
    return {"gap": float(gap[mask].max()), "tokens": int(mask.sum())}


def readings(config: dict, seed: int, sample: List[Served]) -> dict:
    """The number compared, for the program and for its control (the
    reference computed in float8, in the program's place) on the same
    prompts and served tokens: the widest gap of the token each puts
    first."""
    m = model_sizes(config)
    tokens, targets, mask = sequences(sample, config["engine"]["max_seq"])
    ctrl = bitnet.control_tokens(m, seed, tokens, config["torch_dtype"])
    prog, low = bitnet.token_gaps(m, seed, tokens, [targets, ctrl],
                                  config["torch_dtype"])
    return {"program": float(prog[mask].max()),
            "control": float(low[mask].max()), "tokens": int(mask.sum())}


def serve(ctx, open_loop: bool):
    """The whole run of a serve cell; returns the harness's Outcome."""
    from bench.harness import Outcome

    config, mix = ctx.config, ctx.mix
    engine = build_engine(config, ctx.seed)
    warm_up(engine, config, mix)
    requests = traffic.generate(mix, ctx.seed, config["vocab_size"],
                                window_seconds(ctx))
    window = run_window(ctx, engine, requests)
    if open_loop:
        drain_first_tokens(engine, window)
    ctx.read_memory()
    e2e = end_to_end(window)
    observed = {"steps": window.steps, "traced_steps": window.traced_steps,
                "model": model_sizes(config)}
    sample = check_sample(window, ctx.seed, mix["check"]["requests"])
    free(engine)
    del engine
    got = logit_gap(config, ctx.seed, sample)
    limit = config["check"]["logit_gap"]
    checks = {"logit_gap": {"value": got["gap"] if got else float("inf"),
                            "limit": limit}}
    arrived = [s for s in window.served if s.arrival_s < window.seconds]
    failed = sum(1 for s in arrived if s.req.refused or s.req.truncated)
    steps = window.steps
    notes = {"requests_in_window": len(arrived),
             "requests_finished": sum(s.req.done for s in arrived),
             "steps": len(steps),
             # where a stall fell, as [seconds, when]: inside an engine
             # step, or between two steps
             "longest_step": max(
                 ([s["t1"] - s["t0"], s["t0"]] for s in steps), default=None),
             "longest_between_steps": max(
                 ([b["t0"] - a["t1"], a["t1"]]
                  for a, b in zip(steps, steps[1:])), default=None),
             "checked_requests": len(sample),
             "checked_tokens": got["tokens"] if got else 0,
             "generator_late_p95_ms": traffic.percentile(
                 window.late_s, 95) * 1e3 if window.late_s else None}
    return Outcome(end_to_end=e2e, checks=checks, attempted=len(arrived),
                   failed=failed, observed=observed, notes=notes)
