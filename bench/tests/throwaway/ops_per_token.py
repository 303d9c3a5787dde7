"""A per-layer metric reader that is not in bench/readers/, for
bench/tests/test_add_cell.py to add as a file: the operations a trained
token needs by the arithmetic the configuration file names. A count from
shapes, so a CPU run may report it. spec: {}."""

import spec as cells


def read(sources, spec):
    model = sources["model"]
    if "seq" not in model:
        return None
    return cells.named_module(model, "operations").train_flops_per_token(
        model["dims"], model["dims"]["n_layers"], model["seq"])
