"""A later PR adds another architecture (its configuration, its plain
reference with its leaf table, its operations arithmetic, its probe), a
traffic mix, cells, a reader and
per-layer metrics as NEW files and one entry each in BENCHMARK.json, and
edits no file that is there. Shown in a temporary copy, on the CPU preset,
with the second architecture the program runs at toy size through both the
engine and `loss_fn` (`tiny_gemma`; the files are bench/tests/throwaway/)."""

import filecmp
import json
import os
import shutil

import pytest

import flops
import spec
from test_rehearsal import run_cell

THROWAWAY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "throwaway")


def checkout(tmp_path):
    """A temporary checkout holding a copy of bench/; returns its root and
    the parsed BENCHMARK.json, which `write_benchmark` puts there."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(spec.REPO, "ray_tpu"), root / "ray_tpu")
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        return root, json.load(f)


def write_benchmark(root, bench):
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)


def derive(src, dst, change):
    with open(src) as f:
        doc = json.load(f)
    change(doc)
    with open(dst, "w") as f:
        json.dump(doc, f)


def add_cell(bench, name, config, traffic, metrics):
    """A cell is an entry, and its name appended to the `workloads` of each
    accepted metric it reports: an end-to-end one, or a per-layer one that
    is read the same way in every cell of its kind."""
    bench["workloads"].append({
        "name": name, "config": config, "traffic": traffic, "chips": 1,
        "why": "shows that a cell is an entry"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append(name)


def result_line(name, trace, root):
    done = run_cell(name, 5 + trace, trace, cwd=str(root))
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


def test_add_another_architecture_as_files(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(THROWAWAY)
    import opcount

    root, bench = checkout(tmp_path)
    there = root / "bench"
    shutil.copy(os.path.join(THROWAWAY, "gemma.py"), there / "reference")
    shutil.copy(os.path.join(THROWAWAY, "opcount.py"), there)
    shutil.copy(os.path.join(THROWAWAY, "engine_probe.py"), there / "probes")
    shutil.copy(os.path.join(THROWAWAY, "ops_per_token.py"), there / "readers")
    shutil.copy(os.path.join(THROWAWAY, "gemma.json"),
                there / "configs" / "throwaway.json")
    # The control: the same configuration naming a reference that computes
    # something else (the MLP subtracted from the residual stream).
    with open(os.path.join(THROWAWAY, "gemma.py")) as f:
        sound = f.read()
    assert sound.count("return x + mlp") == 1
    with open(there / "reference" / "gemma_wrong.py", "w") as f:
        f.write(sound.replace("return x + mlp", "return x - mlp"))
    derive(there / "configs" / "throwaway.json",
           there / "configs" / "throwaway-wrong.json",
           lambda d: d.update(reference="gemma_wrong"))
    derive(there / "traffic" / "chat-open-poisson.json",
           there / "traffic" / "throwaway-bursty.json",
           lambda d: d.update(arrival={
               "process": "pareto", "rate_per_s": 3.0, "pareto_alpha": 1.5,
               "flash": {"start_share": 0.3, "length_s": 1.0, "mult": 3.0}}))
    for name, how in (
            ("throwaway.decode_ms", {"reader": "observatory",
                                     "phases": ["decode"], "scale": 1000.0}),
            ("throwaway.ops_per_token", {"reader": "ops_per_token"}),
            ("throwaway.mfu", {"reader": "mfu"})):
        with open(there / "layer_metrics" / f"{name}.json", "w") as f:
            json.dump(how, f)
    for name in ("throwaway", "throwaway-wrong"):
        bench["configs"].append({
            "name": name, "source": "ray_tpu/models/configs.py tiny_gemma",
            "file": f"bench/configs/{name}.json", "reduced": [],
            "why": "shows that another architecture is files"})
        # What a later `model_config` PR does: its serve cell joins a
        # generic per-layer entry's list beside its end-to-end metric's.
        add_cell(bench, f"{name}-serve", name, "throwaway-bursty",
                 ("tpot_p95_ms", "compiles_in_window.steady"))
        add_cell(bench, f"{name}-train", name, "tokens-8x1024",
                 ("train_tokens_per_s_chip",))
    serve_cells = ["throwaway-serve", "throwaway-wrong-serve"]
    train_cells = ["throwaway-train", "throwaway-wrong-train"]
    bench["per_layer"] += [
        {"name": "throwaway.decode_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "serve path",
         "moves": "tpot_p95_ms", "workloads": serve_cells},
        {"name": "throwaway.ops_per_token", "unit": "flop/token",
         "better": "lower", "source": "program_counter", "layer": "programs",
         "moves": "train_tokens_per_s_chip", "workloads": train_cells},
        {"name": "throwaway.mfu", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "programs",
         "moves": "train_tokens_per_s_chip", "workloads": train_cells}]
    write_benchmark(root, bench)

    for trace in (0, 1):
        line, out = result_line("throwaway-serve", trace, root)
        assert line["correct"] is True and line["failed"] == 0
        assert ("(reference gemma, operations opcount, probe engine_probe)"
                in out)
    assert set(line["metrics"]) == {"throwaway.decode_ms",
                                    "compiles_in_window.steady"}
    assert line["metrics"]["throwaway.decode_ms"]["value"] > 0
    assert line["metrics"]["compiles_in_window.steady"]["value"] >= 0
    for trace in (0, 1):
        line, out = result_line("throwaway-train", trace, root)
        assert line["correct"] is True and line["failed"] == 0
    # The arithmetic the file names is the one called, with the further
    # published size in its `dims`; a reader that finds nothing (no MFU
    # off the chip) is left out of the line.
    assert set(line["metrics"]) == {"throwaway.ops_per_token"}
    with open(there / "configs" / "throwaway.json") as f:
        doc = json.load(f)
    dims = spec.dims_of(spec.program_config(doc, "tpu"), doc)
    assert dims["final_logit_softcap"] == 30.0
    with open(os.path.join(spec.BENCH, "traffic", "tokens-8x1024.json")) as f:
        seq = json.load(f)["cpu_preset"]["seq"]
    ops = opcount.train_flops_per_token(dims, 2, seq)
    assert line["metrics"]["throwaway.ops_per_token"]["value"] == ops
    assert ops != flops.train_flops_per_token(dims, 2, seq)
    # The module the file names is the one compared: a result, not correct.
    for name in ("throwaway-wrong-serve", "throwaway-wrong-train"):
        line, out = result_line(name, 0, root)
        assert line["correct"] is False, out[-3000:]

    # Nothing that was there was edited: only new files, and only these.
    cmp = filecmp.dircmp(spec.BENCH, there, ignore=["__pycache__"])
    assert not cmp.diff_files and not cmp.left_only
    assert sorted(cmp.right_only) == ["opcount.py"]
    for sub, new in (("configs", 2), ("traffic", 1), ("layer_metrics", 3),
                     ("reference", 2), ("readers", 1), ("probes", 1)):
        sc = cmp.subdirs[sub]
        assert not sc.diff_files and not sc.left_only, sub
        assert len(sc.right_only) == new, (sub, sc.right_only)


def test_mfu_counts_with_the_operations_module_the_file_names(monkeypatch):
    from readers import mfu

    monkeypatch.syspath_prepend(THROWAWAY)
    import opcount

    dims = {"vocab_size": 256, "d_model": 64, "d_ff": 256, "n_layers": 2,
            "n_heads": 4, "n_kv_heads": 1, "head_dim": 16,
            "final_logit_softcap": 30.0}
    sources = {"client": {"tokens_per_s_chip": 1.0e6},
               "model": {"dims": dims, "seq": 32, "operations": "opcount",
                         "device": {"platform": "tpu", "kind": "TPU v5 lite"}}}
    peak = flops.peaks("TPU v5 lite")["bf16_flops_per_s"]
    ours = opcount.train_flops_per_token(dims, 2, 32)
    assert mfu.read(sources, {}) == pytest.approx(100.0 * 1.0e6 * ours / peak)
    sources["model"]["operations"] = "flops"
    assert mfu.read(sources, {}) == pytest.approx(
        100.0 * 1.0e6 * flops.train_flops_per_token(dims, 2, 32) / peak)
