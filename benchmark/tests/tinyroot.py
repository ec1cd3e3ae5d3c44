"""A copy of the benchmark's data files at a size a CPU test can hold:
the real BENCHMARK.json, traffic, layer and peak files with only the
sizes cut, the kernel allowed to run in interpret mode, and the CPU's
own trace line and operation names in place of the TPU's.  The entries
of ``data/serve_cell.json`` are added, so that the open-loop driver kind
stays exercised though no cell of BENCHMARK.json uses it today."""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)

#: what a tiny run changes: in every configuration, and in a traffic file
#: by its driver ``kind``
TINY_CONFIG = {"rows_n": 3000, "train_tile": 1024,
               "require": {"tuning_source": "default", "interpret": True}}
TINY_SWEEP = {"batch_rows": 64, "pool_batches": 2, "check_rows": 8,
              "trace_seconds": 1}
TINY_TRAFFIC = {
    "sweep": TINY_SWEEP,
    "sweep_ip": TINY_SWEEP,
    "sweep_range": {**TINY_SWEEP, "check_heavy_rows": 2, "shares": {
        "unrelated": 44, "small_family": 16, "heavy_family": 4}},
    "openloop": {"rate_rps": 40, "buckets": [8, 64], "pool_rows": 256,
              "mix": [{"rows": 1, "weight": 60}, {"rows": 8, "weight": 25},
                      {"rows": 64, "weight": 15}],
              "check_requests": 6, "check_rows_per_request": 8,
              "trace_seconds": 1, "trace_lead_seconds": 0.5,
              "drain_seconds": 5},
}
CPU_KIND = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
            "trace_plane": "^/host:CPU$", "trace_line": "^tf_XLAPjRtCpuClient"}
#: any compiled CPU operation stands in for the TPU names the layer
#: files hold
CPU_PATTERN = "."


def load_bench() -> dict:
    """BENCHMARK.json with the entries of ``data/serve_cell.json``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, "data", "serve_cell.json")) as f:
        extra = json.load(f)
    for key in ("workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + extra[key]
    return bench


def make(tmp: str) -> str:
    import jax

    root = os.path.join(tmp, "root")
    data = os.path.join(root, "benchmark")
    os.makedirs(data)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(load_bench(), f)
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(data, sub))
    shutil.copy(os.path.join(BENCH_DIR, "peaks.json"), data)

    def edit(path, change):
        with open(path) as f:
            obj = json.load(f)
        change(obj)
        with open(path, "w") as f:
            json.dump(obj, f)

    for name in os.listdir(os.path.join(data, "configs")):
        edit(os.path.join(data, "configs", name),
             lambda o: o.update(TINY_CONFIG))
    for name in os.listdir(os.path.join(data, "traffic")):
        edit(os.path.join(data, "traffic", name),
             lambda o: o.update(TINY_TRAFFIC[o["kind"]]))
    for name in os.listdir(os.path.join(data, "layers")):
        def cpu_names(o):
            for key in ("pattern", "minus_pattern"):
                if key in o["reader"]:
                    o["reader"][key] = CPU_PATTERN
        edit(os.path.join(data, "layers", name), cpu_names)
    kind = jax.devices()[0].device_kind
    edit(os.path.join(data, "peaks.json"),
         lambda o: o["kinds"].update({kind: CPU_KIND}))
    return root
