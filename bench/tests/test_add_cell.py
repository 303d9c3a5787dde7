"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as NEW files and one entry each in BENCHMARK.json, and edits no file
that is there. Shown in a temporary copy, on the CPU preset."""

import filecmp
import json
import os
import shutil

import spec
from test_rehearsal import run_cell


def test_add_a_cell_as_files(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(spec.REPO, "ray_tpu"), root / "ray_tpu")
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def derive(rel_from, rel_to, change):
        with open(root / "bench" / rel_from) as f:
            doc = json.load(f)
        change(doc)
        with open(root / "bench" / rel_to, "w") as f:
            json.dump(doc, f)

    derive("configs/qwen3-4b-serve.json", "configs/throwaway.json",
           lambda d: d["cpu_preset"]["engine"].update(num_slots=2))
    derive("traffic/chat-open-poisson.json", "traffic/throwaway-bursty.json",
           lambda d: d.update(arrival={"process": "pareto", "rate_per_s": 3.0,
                                       "pareto_alpha": 1.5}))
    with open(root / "bench" / "layer_metrics" / "throwaway.decode_ms.json",
              "w") as f:
        json.dump({"reader": "observatory", "phases": ["decode"],
                   "scale": 1000.0}, f)
    bench["configs"].append({
        "name": "throwaway", "source": bench["configs"][0]["source"],
        "file": "bench/configs/throwaway.json", "reduced": [],
        "why": "shows that a configuration is a file"})
    bench["workloads"].append({
        "name": "throwaway-cell", "config": "throwaway",
        "traffic": "throwaway-bursty", "chips": 1,
        "why": "shows that a cell is an entry"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append("throwaway-cell")
    bench["per_layer"].append({
        "name": "throwaway.decode_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "serve path",
        "moves": "tpot_p95_ms", "workloads": ["throwaway-cell"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    for trace in (0, 1):
        done = run_cell("throwaway-cell", 5, trace, cwd=str(root))
        assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
        line = json.loads(done.stdout.strip().splitlines()[-1])
        assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"throwaway.decode_ms"}
    assert line["metrics"]["throwaway.decode_ms"]["value"] > 0
    # Nothing that was there was edited.
    cmp = filecmp.dircmp(spec.BENCH, root / "bench", ignore=["__pycache__"])
    assert not cmp.diff_files and not cmp.left_only
    assert sorted(cmp.right_only) == []  # new files are in subdirectories
    for sub in ("configs", "traffic", "layer_metrics"):
        sc = cmp.subdirs[sub]
        assert not sc.diff_files and not sc.left_only and len(sc.right_only) == 1
