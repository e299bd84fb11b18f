"""Launch an LLM serving graph: agg | agg_router | disagg | disagg_router.

Spawns the infra planes (statestore + bus), an HTTP discovery frontend,
N serving workers, and (disagg graphs) a remote prefill worker — the
process shapes of the reference's example graphs
(`examples/llm/graphs/{agg,agg_router,disagg,disagg_router}.py`), using
this framework's launcher for every role.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

try:
    import yaml
except ImportError:  # configs are optional
    yaml = None

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from dynamo_tpu.runtime.chips import place_workers  # noqa: E402

GRAPHS = ("agg", "agg_router", "disagg", "disagg_router")


def spawn(args, extra_env=None):
    env = dict(os.environ, PYTHONPATH=REPO)
    env.update(extra_env or {})
    return subprocess.Popen([sys.executable, *args], env=env)


def main() -> None:
    p = argparse.ArgumentParser(description="launch an LLM serving graph")
    p.add_argument("graph", choices=GRAPHS)
    p.add_argument("--model-path", required=True)
    p.add_argument("--model-name", default=None)
    p.add_argument("--workers", type=int, default=1)
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--statestore-port", type=int, default=37901)
    p.add_argument("--bus-port", type=int, default=37902)
    p.add_argument("--config", default=None, help="YAML flag overrides")
    p.add_argument("--max-local-prefill-length", type=int, default=512)
    args = p.parse_args()

    overrides = {}
    cfg_path = args.config or os.path.join(
        os.path.dirname(__file__), "configs", f"{args.graph}.yaml"
    )
    if yaml is not None and os.path.exists(cfg_path):
        with open(cfg_path) as f:
            overrides = yaml.safe_load(f) or {}

    ss = f"127.0.0.1:{args.statestore_port}"
    bus = f"127.0.0.1:{args.bus_port}"
    name = args.model_name or os.path.basename(os.path.normpath(args.model_path))
    router_mode = "kv" if args.graph.endswith("router") else "round_robin"

    procs = [
        spawn(["-m", "dynamo_tpu.runtime.statestore", "--port",
               str(args.statestore_port)]),
        spawn(["-m", "dynamo_tpu.runtime.bus", "--port", str(args.bus_port)]),
    ]
    time.sleep(1.0)
    procs.append(spawn([
        "-m", "dynamo_tpu.cli.run", "in=http", "out=discover",
        "--statestore", ss, "--bus", bus, "--port", str(args.port),
        "--router-mode", router_mode,
        *(["--model-path", args.model_path] if router_mode == "kv" else []),
    ]))

    worker_flags = [
        "--model-path", args.model_path, "--model-name", name,
        "--statestore", ss, "--bus", bus,
    ]
    for k, v in (overrides.get("worker") or {}).items():
        worker_flags += [f"--{k.replace('_', '-')}", str(v)]
    disagg = args.graph.startswith("disagg")
    # every engine process (workers + the prefill worker) gets one distinct
    # chip through the environment libtpu reads; this parent stays off JAX
    chip_envs = place_workers(args.workers + (1 if disagg else 0))
    for i in range(args.workers):
        procs.append(spawn([
            "-m", "dynamo_tpu.cli.run", "in=dyn://dynamo.backend.generate",
            "out=jax", *worker_flags,
            *(["--disagg", "decode", "--max-local-prefill-length",
               str(args.max_local_prefill_length)] if disagg else []),
        ], chip_envs[i]))
    if disagg:
        procs.append(spawn([
            "-m", "dynamo_tpu.cli.run", "in=prefill:dynamo", "out=jax",
            "--model-path", args.model_path,
            "--statestore", ss, "--bus", bus,
        ], chip_envs[-1]))

    print(f"[launch] {args.graph}: frontend http://127.0.0.1:{args.port} "
          f"({args.workers} worker(s){' + prefill' if disagg else ''}, "
          f"routing={router_mode})")
    try:
        signal.pause()
    except KeyboardInterrupt:
        pass
    finally:
        for proc in reversed(procs):
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=35)
            except subprocess.TimeoutExpired:
                proc.kill()


if __name__ == "__main__":
    main()
