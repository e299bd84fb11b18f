"""The system under test: the normal served path, wrapped.

    python3 benchmark/server_child.py [--trace-dir DIR] -- <cli.run arguments>

Calls ``dynamo_tpu.cli.run.amain`` with the arguments after ``--`` (``in=http
out=jax`` and the configuration's flags): the same entry, engine, scheduler
and cache a deployment runs. Only with ``--trace-dir`` it also starts a
thread that waits for ``DIR/start`` and ``DIR/stop`` and brackets them with
``jax.profiler.start_trace(DIR)`` / ``stop_trace()``, then writes
``DIR/done``: only the process that holds the chip can trace it, and the
hook stays in the benchmark's own file. Without it the wrapper adds nothing.
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def trace_on_request(trace_dir: str) -> None:
    def wait_for(name: str) -> None:
        while not os.path.exists(os.path.join(trace_dir, name)):
            time.sleep(0.02)

    wait_for("start")
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # device events are what is read
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    open(os.path.join(trace_dir, "started"), "w").close()
    wait_for("stop")
    jax.profiler.stop_trace()
    open(os.path.join(trace_dir, "done"), "w").close()


def main(argv: list) -> None:
    split = argv.index("--")
    own, served = argv[:split], argv[split + 1:]
    if own[:1] == ["--trace-dir"]:
        threading.Thread(
            target=trace_on_request, args=(own[1],), daemon=True
        ).start()
    from dynamo_tpu.cli.run import amain

    try:
        asyncio.run(amain(served))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main(sys.argv[1:])
