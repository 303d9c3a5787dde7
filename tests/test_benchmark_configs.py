"""Every configuration `BENCHMARK.json` lists names its plain reference,
its operations arithmetic and its further published sizes, and the
program's named config agrees with the file, a case a configuration: what
`bench/spec.py` refuses a file for before the runtime starts, held here in
the repo's own tier-1 (bench/tests/ has the harness's own tests)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import spec  # noqa: E402

DENSE_DIMS = {"vocab_size", "d_model", "d_ff", "n_layers", "n_heads",
              "n_kv_heads", "head_dim", "norm_eps", "rope_theta"}


def listed():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", listed()["configs"],
                         ids=lambda c: c["name"])
def test_a_listed_configuration_is_held_to_its_three_keys(entry):
    with open(os.path.join(REPO, entry["file"])) as f:
        doc = json.load(f)
    # `reference` and `operations`: named, present, with every function
    # the harness calls; `published_extra`: stated and mapped to fields
    # the program's config has.
    spec.check_config(doc, entry["file"])
    for key, (_package, needs) in spec.MODULES.items():
        module = spec.named_module(doc, key)
        assert all(callable(getattr(module, f)) for f in needs), key
    # The file's published sizes are the program's (depth alone may be
    # reduced, and then the entry says so), the mapped ones included.
    cfg = spec.program_config(doc, "tpu")
    assert cfg.n_layers == doc["num_hidden_layers"]
    from ray_tpu.models import configs

    # The published depth is the named model's, or, where the program
    # names the cut itself (a hybrid's layer pattern is cut with its
    # depth), what the file states under `published`.
    full_depth = (doc.get("published") or {}).get(
        "num_hidden_layers", configs.get_config(doc["model"]).n_layers)
    assert ("num_hidden_layers" in entry["reduced"]) == (
        cfg.n_layers != full_depth)
    extra = doc.get("published_extra") or {}
    dims = spec.dims_of(cfg, doc)
    assert set(dims) == DENSE_DIMS | set(extra.values())
    for key, field in extra.items():
        # A sequence is a list in the file and a tuple in `dims`.
        assert spec._same(doc[key], dims[field]), key
    # Some cell runs it, and its rehearsal model exists.
    assert any(w["config"] == entry["name"] for w in listed()["workloads"])
    assert spec.program_config(spec._with_preset(doc, "cpu"), "cpu")
