"""What a cell is made of, found by name: BENCHMARK.json's entry, the
configuration's file with the reference, the operations arithmetic and the
probe it names, the traffic mix's file and the per-layer metrics' files.
Nothing here knows a cell, a configuration, an architecture or a metric by
name, so a later PR adds one as files and one entry, and edits nothing."""

from __future__ import annotations

import ast
import importlib
import json
import os
from typing import Dict, List, Set

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
CACHE = os.path.join(REPO, ".cache", "bench")

# The model's public config.json key -> the program's TransformerConfig
# field. These are the sizes every decoder's configuration file states and
# the program's named config has to agree with (depth alone may be
# `reduced`). What only some architectures state (experts, a window, latent
# ranks) a file adds itself, in the same form, under `published_extra`.
PUBLISHED_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}

# The three modules a configuration file names: the key in the file -> the
# package under bench/ the module lies in ("" is bench/ itself) and the
# functions it has to define. There is no default for any.
MODULES = {
    "reference": ("reference", ("hidden_layerwise", "logits_rows",
                                "loss_and_grads", "loss_layerwise",
                                "leaf_init")),
    "operations": ("", ("train_flops_per_token",)),
    "probe": ("probes", ("prefill_logits",)),
}


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _with_preset(doc: Dict, platform: str) -> Dict:
    """On the CPU rehearsal a file's `cpu_preset` overrides its top level
    (one level deep for dict values); on the chip it is ignored."""
    out = {k: v for k, v in doc.items() if k != "cpu_preset"}
    if platform != "cpu":
        return out
    for k, v in (doc.get("cpu_preset") or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


def load_cell(workload: str, platform: str) -> Dict:
    bench = _load(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    doc = _load(os.path.join(REPO, config["file"]))
    check_config(doc, config["file"])
    from traffic.generate import load_mix

    def applies(metric: Dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "chips": cell["chips"],
        "config_name": config["name"],
        "config": _with_preset(doc, platform),
        "traffic": _with_preset(load_mix(cell["traffic"]), platform),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def layer_metric_spec(name: str) -> Dict:
    return _load(os.path.join(BENCH, "layer_metrics", f"{name}.json"))


def _defined_names(path: str) -> Set[str]:
    """The names a module's source binds at its top level, read without
    importing it: the process that runs a cell stays clear of JAX."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    names: Set[str] = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, ast.Assign):
            names.update(t.id for t in node.targets
                         if isinstance(t, ast.Name))
    return names


def check_config(doc: Dict, path: str) -> None:
    """Refuse, in one line that names the file and the fault, a
    configuration file that does not name its reference, its operations
    arithmetic and its probe, names a module that is missing or lacks a
    function the harness calls, or maps a size to a field the program's
    config lacks.
    Called before the runtime starts and before any chip is waited for."""
    for key, (package, needs) in MODULES.items():
        name = doc.get(key)
        if not isinstance(name, str) or not name.isidentifier():
            raise SystemExit(
                f"bench: {path} names no {key!r} module (a file "
                f"bench/{package + '/' if package else ''}<name>.py that "
                f"defines {', '.join(needs)}); there is no default")
        source = os.path.join(BENCH, package, name + ".py")
        if not os.path.isfile(source):
            raise SystemExit(f"bench: {path} names the {key} module "
                             f"{name!r}, and there is no "
                             f"{os.path.relpath(source, REPO)}")
        missing = sorted(set(needs) - _defined_names(source))
        if missing:
            raise SystemExit(f"bench: {path} names the {key} module "
                             f"{name!r}, and {os.path.relpath(source, REPO)} "
                             f"does not define {', '.join(missing)}")
    extra = doc.get("published_extra") or {}
    if not extra:
        return  # the ten are held on the chip; JAX is not imported for them
    from ray_tpu.models import configs  # imports JAX, initialises nothing

    cfg = configs.get_config(doc["model"])
    faults = ([f"states no {key!r}" for key in extra if key not in doc]
              + [f"maps {key!r} to {field!r}, which the program's config "
                 f"{doc['model']!r} does not have"
                 for key, field in extra.items() if not hasattr(cfg, field)])
    if faults:
        raise SystemExit(f"bench: {path} under published_extra "
                         + "; ".join(faults))


def named_module(doc: Dict, key: str):
    """The module a configuration file names under `key` (a key of
    `MODULES`), imported; `doc` is the file, or anything that carries
    the key on from it. `check_config` has seen that it is there."""
    package = MODULES[key][0]
    return importlib.import_module(
        f"{package}.{doc[key]}" if package else doc[key])


def _published(doc: Dict) -> Dict[str, str]:
    """Every published size of a configuration file, the public config's
    key -> the program's field: the ten and the file's own."""
    return {**PUBLISHED_KEYS, **(doc.get("published_extra") or {})}


def _same(published, held) -> bool:
    """A published size against the program's: a sequence in the file (a
    list) equals one in a frozen config (a tuple) when their items do."""
    if isinstance(published, list) and isinstance(held, (list, tuple)):
        return (len(published) == len(held)
                and all(map(_same, published, held)))
    return published == held


def program_config(doc: Dict, platform: str):
    """The program's TransformerConfig for a configuration file: its named
    model at the file's depth. On the chip every published size in the file
    must be the program's, or the run is of another model."""
    from dataclasses import replace

    from ray_tpu.models import configs

    cfg = configs.get_config(doc["model"])
    if platform != "cpu":
        wrong: List[str] = []
        for key, field in _published(doc).items():
            if key == "num_hidden_layers":
                continue
            if not hasattr(cfg, field):
                wrong.append(f"{key}: the program's config has no {field!r}")
            elif not _same(doc[key], getattr(cfg, field)):
                wrong.append(f"{key}: file {doc[key]!r}, program "
                             f"{getattr(cfg, field)!r}")
        if wrong:
            raise SystemExit("bench: the configuration file and the "
                             f"program's {doc['model']!r} disagree: "
                             + "; ".join(wrong))
        cfg = replace(cfg, n_layers=doc["num_hidden_layers"])
    return cfg


def dims_of(cfg, doc: Dict) -> Dict:
    """The sizes the reference and the operations arithmetic take, as
    plain numbers under the program's field names: the ten, and whatever
    further sizes the configuration file `doc` states (a sequence as the
    program's config holds it, a tuple, which a static argument can be)."""
    return {field: getattr(cfg, field) for field in _published(doc).values()
            if field != "tie_embeddings"}


def leaf_rules(cfg, doc: Dict):
    """`path -> rule` for `weights.make_params`: the `leaf_init` of the
    reference the file names, over `dims_of` and whether the table is tied
    (the reference's other functions read that off the tree; a leaf's rule
    has no tree to read)."""
    leaf_init = named_module(doc, "reference").leaf_init
    m = dict(dims_of(cfg, doc), tie_embeddings=cfg.tie_embeddings)
    return lambda path: leaf_init(path, m)
