"""A configuration file names its reference, its further published sizes,
its operations arithmetic and its probe. Every file BENCHMARK.json lists is
held to that here, a case a configuration, and a file that does not is refused
before the runtime starts, in one line that names the file and the fault."""

import dataclasses
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
    spec.check_config(dict(doc, reference="qwen3", operations="flops",
                           probe="paged_kv"), "gemma.json")
    dims = spec.dims_of(spec.program_config(doc, "tpu"), doc)
    assert set(dims) == DENSE_DIMS | {"final_logit_softcap"}
    assert dims["final_logit_softcap"] == 30.0
    with pytest.raises(SystemExit, match="final_logit_softcapping: file 50.0"):
        spec.program_config(dict(doc, final_logit_softcapping=50.0), "tpu")
    with pytest.raises(SystemExit, match="hidden_size: file 128"):
        spec.program_config(dict(doc, hidden_size=128), "tpu")


def test_a_published_size_may_be_a_sequence(monkeypatch):
    """A layer pattern is a list in the file and a tuple in the program's
    frozen config: equal when their items are, refused when not, and
    handed on in `dims` as a value a static argument can be."""
    from ray_tpu.models import configs

    @dataclasses.dataclass(frozen=True)
    class Patterned(type(configs.get_config("tiny_gemma"))):
        layer_types: tuple = ("mamba", "attention", "mamba")

    fields = {f.name: getattr(configs.get_config("tiny_gemma"), f.name)
              for f in dataclasses.fields(configs.get_config("tiny_gemma"))}
    monkeypatch.setitem(configs.NAMED_CONFIGS, "tiny_patterned",
                        Patterned(**fields))
    doc = load(os.path.join(THROWAWAY, "gemma.json"))
    doc.update(model="tiny_patterned",
               layer_types=["mamba", "attention", "mamba"])
    doc["published_extra"]["layer_types"] = "layer_types"
    spec.check_config(dict(doc, reference="qwen3", operations="flops",
                           probe="paged_kv"), "patterned.json")
    dims = spec.dims_of(spec.program_config(doc, "tpu"), doc)
    assert dims["layer_types"] == ("mamba", "attention", "mamba")
    hash(tuple(sorted(dims.items())))  # as the references' jits take it
    with pytest.raises(SystemExit, match=(
            r"layer_types: file \['mamba', 'mamba', 'attention'\], program "
            r"\('mamba', 'attention', 'mamba'\)")):
        spec.program_config(
            dict(doc, layer_types=["mamba", "mamba", "attention"]), "tpu")
    with pytest.raises(SystemExit, match="layer_types: file"):
        spec.program_config(dict(doc, layer_types=["mamba", "attention"]),
                            "tpu")


def _no_key(key):
    return lambda d: d.pop(key)


FAULTS = {
    "no reference": (_no_key("reference"), "names no 'reference' module"),
    "no operations": (_no_key("operations"), "names no 'operations' module"),
    "no probe": (_no_key("probe"), "names no 'probe' module"),
    "probe missing": (lambda d: d.update(probe="nowhere"),
                      "there is no bench/probes/nowhere.py"),
    "reference without leaf_init": (
        lambda d: d.update(reference="no_leaf_init"),
        "bench/reference/no_leaf_init.py does not define leaf_init"),
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
    shutil.copy(os.path.join(THROWAWAY, "engine_probe.py"), there / "probes")
    with open(os.path.join(THROWAWAY, "gemma.py")) as f:
        whole = f.read()
    for name, had, has in (
            ("partial", "loss_layerwise = ", "_unused = "),
            ("no_leaf_init", "def leaf_init(", "def _unused(")):
        assert whole.count(had) == 1
        with open(there / "reference" / f"{name}.py", "w") as f:
            f.write(whole.replace(had, has))
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
