"""A configuration file names its reference, its further published sizes
and its operations arithmetic. Every file BENCHMARK.json lists is held to
that here, a case a configuration, and a file that does not is refused
before the runtime starts, in one line that names the file and the fault."""

import json
import os
import shutil

import pytest

import spec
from test_add_cell import THROWAWAY, add_cell, checkout, derive, write_benchmark
from test_rehearsal import run_cell

DENSE_DIMS = {"vocab_size", "d_model", "d_ff", "n_layers", "n_heads",
              "n_kv_heads", "head_dim", "norm_eps", "rope_theta"}


def configs():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        return json.load(f)["configs"]


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", configs(), ids=lambda c: c["name"])
def test_a_listed_configuration_names_what_the_harness_calls(entry):
    doc = load(os.path.join(spec.REPO, entry["file"]))
    spec.check_config(doc, entry["file"])
    for key, (_package, needs) in spec.MODULES.items():
        module = spec.named_module(doc, key)  # imports on the CPU
        assert all(callable(getattr(module, f)) for f in needs)
    # The file's published sizes are the program's named config's (depth
    # apart), and every field it maps exists there.
    cfg = spec.program_config(doc, "tpu")
    assert cfg.n_layers == doc["num_hidden_layers"]
    dims = spec.dims_of(cfg, doc)
    extra = set((doc.get("published_extra") or {}).values())
    assert set(dims) == DENSE_DIMS | extra
    # The rehearsal's model exists too.
    assert spec.program_config(spec._with_preset(doc, "cpu"), "cpu")


def test_further_published_sizes_are_held_and_handed_on():
    doc = load(os.path.join(THROWAWAY, "gemma.json"))
    spec.check_config(dict(doc, reference="qwen3", operations="flops"),
                      "gemma.json")
    dims = spec.dims_of(spec.program_config(doc, "tpu"), doc)
    assert set(dims) == DENSE_DIMS | {"final_logit_softcap"}
    assert dims["final_logit_softcap"] == 30.0
    with pytest.raises(SystemExit, match="final_logit_softcapping: file 50.0"):
        spec.program_config(dict(doc, final_logit_softcapping=50.0), "tpu")
    with pytest.raises(SystemExit, match="hidden_size: file 128"):
        spec.program_config(dict(doc, hidden_size=128), "tpu")


def _no_key(key):
    return lambda d: d.pop(key)


FAULTS = {
    "no reference": (_no_key("reference"), "names no 'reference' module"),
    "no operations": (_no_key("operations"), "names no 'operations' module"),
    "reference missing": (lambda d: d.update(reference="nowhere"),
                          "there is no bench/reference/nowhere.py"),
    "reference lacks a function": (
        lambda d: d.update(reference="partial"),
        "bench/reference/partial.py does not define loss_layerwise"),
    "operations lacks its function": (
        lambda d: d.update(operations="peaks_only"),
        "bench/peaks_only.py does not define train_flops_per_token"),
    "mapped field the program lacks": (
        lambda d: d["published_extra"].update(
            final_logit_softcapping="no_such_field"),
        "to 'no_such_field', which the program's config 'tiny_gemma' does "
        "not have"),
    "mapped size the file does not state": (
        lambda d: d["published_extra"].update(num_experts="num_experts"),
        "states no 'num_experts'"),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_faulty_configuration_file_is_refused_before_the_runtime(
        tmp_path, fault):
    change, says = FAULTS[fault]
    root, bench = checkout(tmp_path)
    there = root / "bench"
    shutil.copy(os.path.join(THROWAWAY, "gemma.py"), there / "reference")
    shutil.copy(os.path.join(THROWAWAY, "opcount.py"), there)
    with open(os.path.join(THROWAWAY, "gemma.py")) as f:
        whole = f.read()
    with open(there / "reference" / "partial.py", "w") as f:
        f.write(whole.replace("loss_layerwise = ", "_unused = "))
    with open(there / "peaks_only.py", "w") as f:
        f.write("def peaks(kind):\n    return {}\n")
    derive(os.path.join(THROWAWAY, "gemma.json"),
           there / "configs" / "faulty.json", change)
    bench["configs"].append({
        "name": "faulty", "source": "none", "reduced": [],
        "file": "bench/configs/faulty.json", "why": "is refused"})
    add_cell(bench, "faulty-train", "faulty", "tokens-8x1024",
             ("train_tokens_per_s_chip",))
    write_benchmark(root, bench)
    done = run_cell("faulty-train", 3, 0, cwd=str(root))
    assert done.returncode != 0
    # One line, naming the file and the fault, and nothing started: no
    # [bench] line (the first comes before `rt.init()`), no result.
    said = done.stderr.strip().splitlines()
    assert len(said) == 1 and said[0].startswith(
        "bench: bench/configs/faulty.json"), done.stderr[-2000:]
    assert says in said[0]
    assert done.stdout.strip() == ""
