"""`per_layer` is a list of metrics, not of cells: one entry and one data
file a metric and `moves`, listing the cells it is read in. The contract
caps the list at 128 and PR 56 filled it with copies that differed by a
cell's suffix alone (PR 60 folded 56 of them into 16); a new cell joins a
generic entry's `workloads` and brings entries only for what is its
model's own."""

import collections
import json
import os

import spec
from test_rehearsal import cells as benchmark

CAP = 128  # the contract's most
LAYER_METRICS = os.path.join(spec.BENCH, "layer_metrics")


def test_every_entry_has_its_data_file_and_every_data_file_its_entry():
    names = [m["name"] for m in benchmark()["per_layer"]]
    assert len(names) <= CAP
    assert len(names) == len(set(names))
    files = {f[:-len(".json")] for f in os.listdir(LAYER_METRICS)}
    assert set(names) == files


# Copies PR 56 brought under a fresh stem and PR 60 left (ISSUE 60 keeps
# "the other 70 as the parent has them"): same reader, same quantity, same
# `moves`, and the first name of each pair says a thing that is not true of
# the second's cell, so folding one means renaming an accepted entry. The
# next `benchmark` PR folds them under names both cells bear and frees 3
# places (`PERF.md` section 7). Nothing is added to this list.
KNOWN_COPIES = {
    # the reader counts live rows of any recurrent state; LFM2's is a
    # convolution's window and not a state-space state
    ("ssm.live_row_share", "conv.live_row_share.lfm2"),
    # `held_*` is of a held eighth of the experts; LFM2 holds them all and
    # `moe_share` is there for its expert layers after two dense ones
    ("moe.held_experts_hit_share", "moe.experts_hit_share.lfm2"),
    ("moe.held_load_max_over_mean", "moe.load_max_over_mean.lfm2"),
}


def test_no_entry_is_a_copy_of_another():
    """Two entries that move the same end-to-end metric and whose data
    files are equal but for `note` are one metric written twice, whatever
    their names: the second cell belongs in the first entry's `workloads`.
    An entry read from the device trace by a pattern (`match` or
    `contains_op`) is a cell's own, whatever its pattern says today: it
    holds that cell's slot count and widths, and a kernel that changes for
    one model parts it from the others."""
    alike = collections.defaultdict(list)
    for m in benchmark()["per_layer"]:
        how = spec.layer_metric_spec(m["name"])
        if "match" in how or "contains_op" in how:
            continue
        how.pop("note", None)
        alike[m["moves"], json.dumps(how, sort_keys=True)].append(m["name"])
    copies = {(a, b) for names in alike.values()
              for i, a in enumerate(names) for b in names[i + 1:]}
    assert copies == KNOWN_COPIES, copies ^ KNOWN_COPIES


def test_every_workloads_list_names_cells():
    bench = benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"] + bench["end_to_end"]:
        listed = m.get("workloads", cells)
        assert listed and set(listed) <= set(cells), m["name"]
        assert len(listed) == len(set(listed)), m["name"]
        # In the order of BENCHMARK.json's cells, so that two lists of the
        # same cells are the same list.
        assert listed == [c for c in cells if c in listed], m["name"]
