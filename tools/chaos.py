"""Composition chaos driver: seeded schedules, replay, shrink.

Runs a :class:`~dynamo_tpu.runtime.chaos.ChaosRunner` mini-cluster under a
seeded fault schedule and judges it with the cluster invariant suite
(docs/chaos.md):

    python tools/chaos.py run --seed 7            # generate + run
    python tools/chaos.py run --seed 7 --schedule-only   # just the JSON
    python tools/chaos.py replay runs/x/schedule.json    # bit-faithful rerun
    python tools/chaos.py shrink runs/x/schedule.json    # 1-minimal repro

Exit contract: 0 = every invariant held,
2 = an invariant violation (artifacts written to --out), 1 = the run
itself could not execute. ``run --seed N`` twice emits byte-identical
schedule JSON; ``replay`` of a violating schedule reproduces it; ``shrink``
greedily drops events while the violation persists and writes the strictly
smaller schedule.

``--mock`` swaps real tiny engines for the deterministic token mock
(kill/delay/blackout/drain legs only — no KV pages to corrupt or migrate);
default is real engines on the virtual CPU mesh.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the virtual 8-device CPU mesh (tests/conftest.py contract): must happen
# before jax is first imported, and only for non-hardware runs (envknobs is
# pre-jax safe — pure env parsing)
from dynamo_tpu.runtime.envknobs import env_flag  # noqa: E402

if not env_flag("DYN_TPU_TESTS_REAL", False):
    from __graft_entry__ import _ensure_devices  # noqa: E402

    _ensure_devices(8)


def _build_engines(n: int):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return [
        JaxServingEngine(
            cfg, params,
            EngineConfig(max_slots=4, kv_block_size=8, max_model_len=256),
        )
        for _ in range(n)
    ]


def _execute(schedule, mock: bool, out_dir: str):
    """Run one schedule; returns (report, engines_to_close)."""
    from dynamo_tpu.runtime.chaos import ChaosRunner

    engines = None if mock else _build_engines(schedule.n_workers)
    runner = ChaosRunner(schedule, engines=engines)
    try:
        report = asyncio.run(runner.run())
    finally:
        for e in engines or []:
            try:
                e.close()
            except Exception:
                pass
    report.write(out_dir)
    return report


def _print_report(report, out_dir: str) -> None:
    print(json.dumps({
        "ok": report.ok,
        "seed": report.schedule.seed,
        "events": len(report.schedule.events),
        "violations": [v.to_dict() for v in report.violations],
        "invariants": report.invariants,
        "stats": report.stats,
        "out": out_dir,
    }, sort_keys=True, indent=2))


def cmd_run(args) -> int:
    from dynamo_tpu.runtime.chaos import ChaosPolicy, ChaosSchedule

    pol = ChaosPolicy.from_env()
    schedule = ChaosSchedule.generate(
        seed=args.seed if args.seed is not None else pol.seed,
        n_workers=args.workers,
        horizon=args.horizon if args.horizon is not None else pol.duration,
        max_events=args.events if args.events is not None else pol.max_events,
        weights=pol.weights,
    )
    if args.schedule_only:
        print(schedule.to_json())
        return 0
    report = _execute(schedule, args.mock, args.out)
    _print_report(report, args.out)
    return 0 if report.ok else 2


def cmd_replay(args) -> int:
    from dynamo_tpu.runtime.chaos import ChaosSchedule

    with open(args.schedule) as f:
        schedule = ChaosSchedule.from_json(f.read())
    report = _execute(schedule, args.mock, args.out)
    _print_report(report, args.out)
    return 0 if report.ok else 2


def cmd_shrink(args) -> int:
    from dynamo_tpu.runtime.chaos import ChaosSchedule, shrink_schedule

    with open(args.schedule) as f:
        schedule = ChaosSchedule.from_json(f.read())

    def violates(candidate) -> bool:
        sub = os.path.join(args.out, "attempt")
        return not _execute(candidate, args.mock, sub).ok

    try:
        small = shrink_schedule(schedule, violates, log=print)
    except ValueError as e:
        print(f"shrink: {e}", file=sys.stderr)
        return 1
    out_path = os.path.join(args.out, "schedule.min.json")
    os.makedirs(args.out, exist_ok=True)
    with open(out_path, "w") as f:
        f.write(small.to_json())
    print(json.dumps({
        "events_before": len(schedule.events),
        "events_after": len(small.events),
        "schedule": out_path,
    }, sort_keys=True, indent=2))
    return 2  # a shrunk schedule is by construction still violating


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    runp = sub.add_parser("run", help="generate a schedule from a seed and run it")
    runp.add_argument("--seed", type=int, default=None)
    runp.add_argument("--workers", type=int, default=3)
    runp.add_argument("--horizon", type=float, default=None,
                      help="schedule horizon seconds (DYN_TPU_CHAOS_DURATION)")
    runp.add_argument("--events", type=int, default=None,
                      help="max events (DYN_TPU_CHAOS_EVENTS)")
    runp.add_argument("--schedule-only", action="store_true",
                      help="print the canonical schedule JSON and exit")
    runp.set_defaults(fn=cmd_run)

    repp = sub.add_parser("replay", help="re-run a dumped schedule bit-faithfully")
    repp.add_argument("schedule", help="path to schedule.json")
    repp.set_defaults(fn=cmd_replay)

    shrp = sub.add_parser("shrink", help="greedily minimize a violating schedule")
    shrp.add_argument("schedule", help="path to schedule.json")
    shrp.set_defaults(fn=cmd_shrink)

    for s in (runp, repp, shrp):
        s.add_argument("--out", default="chaos-run",
                       help="run directory for artifacts")
        s.add_argument("--mock", action="store_true",
                       help="token-mock fleet instead of real tiny engines")

    args = p.parse_args(argv)
    try:
        return args.fn(args)
    except (OSError, ValueError, RuntimeError) as e:
        print(f"chaos: cannot run: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
