"""End-to-end serving driver (the paper's kind: inference acceleration):
offline-quantize a BitNet-style model to ternary weights and stream batched
requests through the continuous-batching engine.  With ``--legion`` (on by
default) every prefill/decode step's projection GEMMs also execute through
the D-Legion runtime, producing per-request traffic and cycle tallies.

    PYTHONPATH=src python examples/serve_bitnet.py --requests 12 --slots 4
"""
import argparse
import time

import jax
import numpy as np

from repro.models import build_model
from repro.serve import ServeEngine
from repro.serve.engine import prepare_params
from repro.serve.kv_cache import plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bitnet-1.58b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (needs a real accelerator)")
    ap.add_argument("--no-legion", action="store_true",
                    help="skip the D-Legion serve backend tallies")
    ap.add_argument("--legions", type=int, default=8,
                    help="Legion count for the accelerator model")
    ap.add_argument("--sharded", action="store_true",
                    help="run step GEMMs through the ShardedExecutor "
                         "(Legion axis on a JAX mesh axis; set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 first)")
    args = ap.parse_args()

    from repro.configs import get_config, reduced
    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = reduced(cfg)
    api = build_model(cfg)

    budget = plan(cfg, batch=args.slots, max_seq=args.max_seq,
                  hbm_bytes_per_chip=16e9, chips=1)
    print(f"arch={cfg.name}  kv-bytes/token={budget.bytes_per_token}  "
          f"cache={budget.total_bytes/1e6:.1f}MB  fits={budget.fits_hbm}")

    params = api.init(jax.random.PRNGKey(0))
    params = prepare_params(params)   # offline ternary quantization
    eng = ServeEngine(api, params, max_slots=args.slots,
                      max_seq=args.max_seq)

    backend = None
    if not args.no_legion:
        from repro.core import dlegion
        from repro.legion import ShardedExecutor
        from repro.serve import LegionServeBackend

        accel = dlegion(legions=args.legions)
        executor = ShardedExecutor() if args.sharded else None
        backend = LegionServeBackend(accel, cfg, params,
                                     executor=executor).attach(eng)
        print(f"legion backend attached: {accel.name}, every step lowered "
              f"to a Program (projections + act-to-act attention over the "
              f"KV context) through a Machine session "
              f"({backend.machine.backend.name} executor)")

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        prompt = rng.integers(1, cfg.vocab, size=rng.integers(4, 24))
        eng.submit(prompt, max_new_tokens=args.max_new)
    done = eng.run_until_done()
    dt = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests / {tokens} tokens in {dt:.2f}s "
          f"({tokens/dt:.1f} tok/s on this host)")
    for r in done[:3]:
        print(f"  req {r.uid}: prompt[{len(r.prompt)}] -> {r.output}")

    if backend is not None:
        s = backend.summary()
        print(f"D-Legion tallies ({s['prefill_steps']} prefills, "
              f"{s['decode_steps']} decode steps through the runtime):")
        print(f"  per decode token: {s['cycles_per_decode_token']} cycles "
              f"({s['us_per_decode_token']:.3f} us @ 1 GHz)")
        print(f"  total: {s['cycles'] / 1e3:.1f} kcycles, "
              f"weight={s['weight_bytes'] / 1e6:.2f} MB, "
              f"act={s['act_bytes'] / 1e6:.2f} MB, "
              f"psum={s['psum_bytes'] / 1e6:.2f} MB")
        for uid in sorted(backend.per_request)[:3]:
            t = backend.per_request[uid]
            print(f"  req {uid}: prefill[{t.prefill_tokens}] + "
                  f"decode[{t.decode_tokens}] -> {t.cycles} cycles, "
                  f"{t.mem_bytes / 1e3:.1f} KB moved")
        tv, cv = backend.cross_validate(decode_contexts=(16,))
        worst = max([e for v in tv for e in v.errors.values()]
                    + [v.rel_err for v in cv])
        assert all(v.ok for v in tv + cv)
        print(f"  cross-validated vs simulate() ({len(tv)} stage families, "
              f"attention included): worst error {worst * 100:.2f}% — OK")

        # latency-aware admission: measured decode cycles -> tokens/sec
        budget = plan(cfg, batch=args.slots, max_seq=args.max_seq,
                      hbm_bytes_per_chip=16e9, chips=1,
                      cycles_per_token=s["cycles_per_decode_token"],
                      freq_hz=accel.freq_hz)
        print(f"  latency-aware cache budget: "
              f"{budget.tokens_per_sec:,.0f} tok/s per slot "
              f"({budget.batch_tokens_per_sec:,.0f} across {args.slots} "
              f"slots), {budget.seconds_to_fill(args.max_seq) * 1e3:.2f} ms "
              f"to fill a {args.max_seq}-token window")

        # engine view: each batched decode step as one merged graph
        # through the pipelined schedule (overlapped <= serial, asserted)
        if s["decode_steps"]:
            piped = backend.cache_budget(
                batch=args.slots, max_seq=args.max_seq,
                hbm_bytes_per_chip=16e9, chips=1)
            print(f"  engine view (merged batch graphs, pipelined): "
                  f"{s['overlapped_cycles_per_step']:.0f} of "
                  f"{s['serial_cycles_per_step']:.0f} cycles/step "
                  f"(x{s['pipeline_speedup']:.3f}) -> "
                  f"{piped.tokens_per_sec:,.0f} tok/s per slot overlapped")


if __name__ == "__main__":
    main()
