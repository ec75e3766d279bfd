"""Offline serving of one chip's share of an expert-parallel MoE model
(Moonlight-16B-A3B: latent attention, held experts): the mix's whole queue
waits at the window's open, more requests than slots, so the engine runs
full batches all window long.

The engine is built from the configuration through the registry and takes
the weights of ``references/moonlight.py`` made from the seed; the window,
the traffic, the end-to-end numbers and the requests checked are
``bench/serving.py``'s.  Each step's routing counts (``moe_routed``,
``moe_experts_hit`` of the engine's step event) join the step's record for
the per-layer readers.  After the window the checked requests run once
through the plain reference (``logit_gap``).

Set-up ends with a full collection and ``gc.freeze()``, as a long-running
server marks its start-up heap static: the weights' construction, tracing
and warm-up leave a few hundred thousand objects the collector tracks, and
an oldest-generation collection over them would pause an engine step in
the window (``gc_full_s`` in the notes says for how long).  The collector
still runs in the window, over what the window allocates; its pauses there
are counted in the notes (``gc_in_window``: collections, seconds, longest).
"""
from __future__ import annotations

import gc
import time
from typing import List, Optional

import jax
import jax.numpy as jnp

from bench import serving, traffic
from bench.references import moonlight as ref

ROUTING = ("moe_routed", "moe_experts_hit")


def model_config(config: dict):
    """The program's ModelConfig: its registry entry, with every size as
    the configuration file states it."""
    from repro.configs import get_config

    return get_config(config["registry"]).replace(
        layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        d_ff=config["moe_intermediate_size"], vocab=config["vocab_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        router_scoring=config["scoring_func"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling=config["routed_scaling_factor"],
        experts_held=config["experts_held"],
        expert_offset=config["expert_offset"],
        first_k_dense=config["first_k_dense_replace"],
        dense_d_ff=config["intermediate_size"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=config["torch_dtype"], quantization=config["quantization"])


def program_params(w: dict) -> dict:
    """The reference's weights in the program's parameter layout."""
    def attn(lay):
        return {k: lay[k] for k in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")}

    def mlp(lay, prefix=""):
        return {k: lay[prefix + k] for k in ("w1", "w2", "w3")}

    dense, lay = w["dense_layers"], w["layers"]
    return {
        "embed": {"tokens": w["embed"]}, "lm_head": w["lm_head"],
        "ln_f": w["ln_f"],
        "dense_blocks": {"ln1": dense["ln1"], "ln2": dense["ln2"],
                         "attn": attn(dense), "mlp": mlp(dense)},
        "blocks": {"ln1": lay["ln1"], "ln2": lay["ln2"], "attn": attn(lay),
                   "moe": {"router": lay["router"],
                           "router_bias": lay["router_bias"],
                           "experts": mlp(lay, "expert_"),
                           "shared": mlp(lay, "shared_")}},
    }


def build_engine(config: dict, seed: int):
    """Weights from the seed in one jitted call, then the engine."""
    from repro.models import build_model
    from repro.serve import ServeEngine

    api = build_model(model_config(config))
    params = program_params(ref.served_weights(
        ref.sizes(config), seed, jnp.dtype(config["torch_dtype"])))
    want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
        raise ValueError(f"the program's parameter layout changed: {want}")
    jax.block_until_ready(params)
    e = config["engine"]
    return ServeEngine(api, params, max_slots=e["max_slots"],
                       max_seq=e["max_seq"],
                       prefill_chunk_tokens=e["prefill_chunk_tokens"],
                       greedy=True)


def join_routing(steps: List[dict], events: List[dict]) -> None:
    """Add each step event's routing counts to the window's record of that
    step (one event per engine step, in order)."""
    if len(events) != len(steps):
        raise RuntimeError(f"{len(events)} step events for {len(steps)} "
                           f"engine steps")
    for s, ev in zip(steps, events):
        if list(ev.get("positions", [])) != s["positions"]:
            raise RuntimeError("a step event does not match its step")
        s.update({k: ev[k] for k in ROUTING if k in ev})


def readings(config: dict, seed: int, sample) -> dict:
    """The number compared, for the program and for its float8 control, on
    the same prompts and served tokens (``references/moonlight.py``)."""
    tokens, targets, mask = serving.sequences(sample,
                                              config["engine"]["max_seq"])
    return ref.readings(ref.sizes(config), seed, tokens, targets, mask,
                        config["torch_dtype"])


def logit_gap(config: dict, seed: int, sample) -> Optional[dict]:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of the sample."""
    if not sample:
        return None
    tokens, targets, mask = serving.sequences(sample,
                                              config["engine"]["max_seq"])
    (gap,) = ref.token_gaps(ref.sizes(config), seed, tokens, [targets],
                            config["torch_dtype"])
    return {"gap": float(gap[mask].max()), "tokens": int(mask.sum())}


class Pauses:
    """The collector's pauses while attached to ``gc.callbacks``."""

    def __init__(self) -> None:
        self.collections = 0
        self.seconds = 0.0
        self.longest = 0.0
        self._t: Optional[float] = None

    def __call__(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.collections += 1
            self.seconds += d
            self.longest = max(self.longest, d)
            self._t = None


def serve_window(ctx):
    """Build, warm up and run the window: (engine, window, notes on the
    collector)."""
    config, mix = ctx.config, ctx.mix
    engine = build_engine(config, ctx.seed)
    serving.warm_up(engine, config, mix)
    requests = traffic.generate(mix, ctx.seed, config["vocab_size"],
                                serving.window_seconds(ctx))
    events: List[dict] = []
    engine.step_observers.append(events.append)
    gc.collect()
    gc.freeze()
    pauses = Pauses()
    gc.callbacks.append(pauses)
    try:
        window = serving.run_window(ctx, engine, requests)
    finally:
        gc.callbacks.remove(pauses)
        gc.unfreeze()
    engine.step_observers.remove(events.append)
    join_routing(window.steps, events)
    # what one full collection over the unfrozen heap costs: the stall the
    # freeze keeps out of the window
    t = time.perf_counter()
    gc.collect()
    full_s = time.perf_counter() - t
    notes = {"gc_in_window": [pauses.collections, pauses.seconds,
                              pauses.longest],
             "gc_full_s": full_s, "gc_tracked": len(gc.get_objects())}
    return engine, window, notes


def run(ctx):
    from bench.harness import Outcome

    config, mix = ctx.config, ctx.mix
    engine, window, gc_notes = serve_window(ctx)
    ctx.read_memory()
    e2e = serving.end_to_end(window)
    observed = {"steps": window.steps, "traced_steps": window.traced_steps,
                "model": ref.sizes(config)}
    sample = serving.check_sample(window, ctx.seed, mix["check"]["requests"])
    serving.free(engine)
    del engine
    got = logit_gap(config, ctx.seed, sample)
    checks = {"logit_gap": {"value": got["gap"] if got else float("inf"),
                            "limit": config["check"]["logit_gap"]}}
    arrived = [s for s in window.served if s.arrival_s < window.seconds]
    failed = sum(1 for s in arrived if s.req.refused or s.req.truncated)
    steps = window.steps
    decode = [s for s in steps if "moe_experts_hit" in s]
    notes = {"requests_in_window": len(arrived),
             "requests_finished": sum(s.req.done for s in arrived),
             "steps": len(steps),
             "longest_step": max(
                 ([s["t1"] - s["t0"], s["t0"]] for s in steps), default=None),
             "longest_between_steps": max(
                 ([b["t0"] - a["t1"], a["t1"]]
                  for a, b in zip(steps, steps[1:])), default=None),
             "moe_routed": sum(s["moe_routed"] for s in decode),
             "moe_experts_hit": sum(s["moe_experts_hit"] for s in decode),
             "checked_requests": len(sample),
             "checked_tokens": got["tokens"] if got else 0, **gc_notes}
    return Outcome(end_to_end=e2e, checks=checks, attempted=len(arrived),
                   failed=failed, observed=observed, notes=notes)
