"""The benchmark's cells cut to a size a CPU test run can hold.

Every cell of ``BENCHMARK.json`` is rehearsed, each at the cut that its own
``bench/workloads/<cell>.json`` states under ``small``: only the
configuration's scale shrinks; the generator, the values, the format, the
traffic and the limits stay those of ``BENCHMARK.json``.  A cell on more
than one chip runs in a child process that sees as many virtual CPU
devices (``python -m bench.tests.cells`` reads the job from standard input
and prints one JSON object).
"""

import json
import os
import subprocess
import sys
import time

from bench import harness

BM = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = sorted(w["name"] for w in BM["workloads"])


def small_spec(cell: str) -> dict:
    spec = harness.load_cell(cell)
    spec["config"].update(spec["cell"]["small"])
    return spec


def run_small(cell: str, store, *, seeds=(3,), seconds=0.3, trace=False,
              values=None, spec=None, log=None) -> list:
    """Every result of one small run of ``cell`` (or of ``spec``, a cell's
    spec changed by the caller) on this host's JAX, or on as many virtual
    CPU devices as the spec asks chips; ``log`` gets the harness's log
    lines."""
    job = {"spec": spec or small_spec(cell), "store": str(store),
           "seeds": list(seeds), "seconds": seconds, "trace": trace,
           "values": values}
    if job["spec"]["chips"] == 1:
        out = _run(job)
    else:
        out = _run_on_devices(job)
    for msg in out["log"]:
        (log or (lambda m: None))(msg)
    return out["results"]


def _run(job: dict) -> dict:
    from repro.tuning.store import clear_store

    lines = []
    try:
        results = list(harness.run(
            job["spec"], job["seeds"], job["seconds"], job["trace"],
            t0=time.perf_counter(), values=job["values"], log=lines.append,
            store=job["store"]))
    finally:
        clear_store()
    return {"results": results, "log": lines}


def _run_on_devices(job: dict) -> dict:
    chips = job["spec"]["chips"]
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={chips}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(harness.ROOT / "src"),
                                         str(harness.ROOT)])
    p = subprocess.run([sys.executable, "-m", "bench.tests.cells"],
                       input=json.dumps(job), cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    if p.returncode != 0:
        raise RuntimeError(f"the {chips}-device rehearsal failed:\n"
                           f"{p.stderr[-4000:]}")
    return json.loads(p.stdout.splitlines()[-1])


if __name__ == "__main__":
    print(json.dumps(_run(json.loads(sys.stdin.read()))))
