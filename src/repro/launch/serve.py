"""Serving launcher: ConServe deployment driver.

Three modes:
  --engine  : real JAX replicas on the local device — the published config
              in its own dtype (bf16 for qwen3-0.6b) with random weights
              from --seed; add --reduced for the small fp32 CPU model
  --sim     : the calibrated discrete-event cluster runtime
  default   : lower+compile the serve_step for the production mesh
              (prefill + decode programs for the chosen arch), proving the
              deployment's distribution config before touching hardware.

--engine and --sim drive their backend through the ONE shared
`repro.core.runtime.Runtime` contract (submit/run/results + admission
control), so the launcher — like the schedulers — cannot tell the two
scales apart.

  python -m repro.launch.serve --arch qwen3-0.6b [--multi-pod]
                               [--engine [--reduced] | --sim] [--slots N]
                               [--gateway] [--scenario NAME] [--seed S]

--scenario picks a named workload from the scenario library
(`repro.traces.SCENARIOS`); --gateway serves it LIVE through the async
streaming gateway (staged arrivals, per-token event bus) instead of the
offline submit+run batch path — same runtime, same records, plus live
streaming observables.
"""
import argparse


def build_engine(cfg, *, n_slots: int, max_ctx: int,
                 scheduler: str = "conserve", attention_impl: str = "xla",
                 seed: int = 0, **server_kw):
    """The disaggregated engine deployment on the local device: one
    prefill replica and two decode replicas of `cfg`, sharing one
    set of random weights drawn from `seed` in the config's dtype, behind
    an `EngineServer` running `scheduler`. `server_kw` goes to
    `EngineServer` (rotation, prefill_mode, strict_accounting, ...)."""
    import jax
    from repro.core import make_scheduler
    from repro.engine import EngineServer, ReplicaEngine
    from repro.models import build_model

    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    reps = [ReplicaEngine(cfg, params, n_slots=n_slots, max_ctx=max_ctx,
                          replica_id=i, role="prefill" if i == 0 else "decode",
                          attention_impl=attention_impl)
            for i in range(3)]
    return EngineServer(make_scheduler(scheduler), reps, seed=seed,
                        **server_kw)


def _drive(runtime, trace, gateway: bool = False):
    """The whole serving contract, backend-agnostic. With `gateway`, the
    trace is injected live through `repro.serve` (staged arrivals driven by
    an asyncio loop) rather than submitted as one offline batch."""
    from repro.core.metrics import summarize
    if gateway:
        from repro.serve import serve_scenario_live
        recs, gw, _ = serve_scenario_live(runtime, trace)
        h = gw.health()
        print(f"  gateway: {h['n_submitted']} submitted, {h['n_done']} done, "
              f"{h['n_shed']} shed; events: {h['events_seen']}")
    else:
        recs = runtime.serve(trace)
    s = summarize(recs)
    for k in ("ttfet_gmean", "ttfet_p95", "last_tbt_gmean", "e2e_gmean",
              "kv_transfers_per_conv"):
        print(f"  {k}: {s[k]:.4f}")
    waits = [w for w in runtime.queue_waits().values() if w > 0]
    if waits:
        print(f"  admission waits: {len(waits)} conversations, "
              f"max {max(waits):.3f}s (backpressure, not a crash)")
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--engine", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="engine: serve the small fp32 reduction of --arch "
                         "(CPU runs and tests) instead of its published "
                         "config")
    ap.add_argument("--sim", action="store_true")
    ap.add_argument("--scheduler", default="conserve",
                    choices=["conserve", "ampd", "collocated", "full_disagg"])
    ap.add_argument("--n-conversations", type=int, default=12)
    ap.add_argument("--slots", type=int, default=16,
                    help="engine: KV slots per replica (small values "
                         "exercise admission backpressure)")
    ap.add_argument("--no-rotation", action="store_true",
                    help="engine: disable continuous decode rotation "
                         "(adaptive chunk cuts + mid-tail slot refill) and "
                         "fall back to chunk-boundary-only admission — the "
                         "before/after comparison knob")
    ap.add_argument("--prefill-mode", default=None,
                    choices=["jit", "reference"],
                    help="engine: override the (append-)prefill path — "
                         "'jit' = AOT-compiled donated bucket programs "
                         "(replica default), 'reference' = the eager "
                         "per-op oracle — the before/after comparison knob")
    ap.add_argument("--gateway", action="store_true",
                    help="serve LIVE through the async streaming gateway "
                         "(staged arrivals + per-token event bus) instead "
                         "of the offline batch path")
    ap.add_argument("--scenario", default=None,
                    help="named workload from the scenario library "
                         "(pareto_burst, supervisor_worker, hitl_longpark, "
                         "shared_preamble_fleet); default: the classic "
                         "generate_trace workload")
    ap.add_argument("--seed", type=int, default=0,
                    help="scenario seed (byte-identical trace per seed); "
                         "engine: also the seed of the random weights")
    args = ap.parse_args()

    if args.engine:
        from repro.configs import get_config, get_reduced
        from repro.launch.compile_cache import enable_compile_cache
        from repro.traces import TraceConfig, generate_trace

        enable_compile_cache()
        cfg = (get_reduced if args.reduced else get_config)(args.arch)
        srv = build_engine(cfg, n_slots=args.slots, max_ctx=1024,
                           scheduler=args.scheduler, seed=args.seed,
                           rotation=not args.no_rotation,
                           prefill_mode=args.prefill_mode)
        if args.scenario:
            from repro.traces import make_scenario
            trace = make_scenario(args.scenario, args.n_conversations,
                                  seed=args.seed, scale="engine")
        else:
            tc = TraceConfig(first_input_median=150, first_input_max=500,
                             append_median=24, append_max=64,
                             output_median=10, output_max=32, mean_turns=3.0,
                             max_turns=6, tool_mean_s=0.05)
            trace = generate_trace(args.n_conversations, 2.0, cfg=tc)
        _drive(srv, trace, gateway=args.gateway)
        return

    if args.sim:
        from repro.cluster import paper_deployment
        from repro.traces import TraceConfig, generate_trace

        sim = paper_deployment(args.scheduler)
        if args.scenario:
            from repro.traces import make_scenario
            trace = make_scenario(args.scenario, args.n_conversations,
                                  seed=args.seed, scale="paper")
        else:
            trace = generate_trace(args.n_conversations, 1.634,
                                   TraceConfig(seed=17))
        _drive(sim, trace, gateway=args.gateway)
        return

    import os
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    import jax
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_decode_program, build_prefill_program

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    with mesh:
        for name, (fn, a) in (
                ("prefill_32k", build_prefill_program(args.arch, mesh)),
                ("decode_32k", build_decode_program(args.arch, mesh,
                                                    "decode_32k"))):
            compiled = jax.jit(fn).lower(*a).compile()
            print(f"{name}: compiled OK on {mesh.shape}; "
                  f"{compiled.memory_analysis()}")


if __name__ == "__main__":
    main()
