"""bench/flops.py against hand counts for Qwen3-4B."""

import json
import os

import pytest

import flops
import spec


def dims():
    with open(os.path.join(spec.BENCH, "configs", "qwen3-4b-serve.json")) as f:
        doc = json.load(f)
    return {field: doc[key] for key, field in spec.PUBLISHED_KEYS.items()
            if field != "tie_embeddings"}


def test_matrix_parameters_by_hand():
    m = dims()
    # q and o: 2560 x 4096 each; k and v: 2560 x 1024 each; MLP: 3 x 2560 x 9728
    by_hand = 2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728
    assert flops.layer_matrix_params(m) == by_hand == 100_925_440
    assert flops.head_params(m) == 151_936 * 2560 == 388_956_160


def test_train_flops_a_token_at_four_layers():
    m = dims()
    got = flops.train_flops_per_token(m, 4, 1024)
    matrix = 6 * (4 * 100_925_440 + 388_956_160)
    attention = 3 * 4 * 4 * 512 * 32 * 128  # causal: mean context S / 2
    assert got == matrix + attention
    assert got == pytest.approx(4.86e9, rel=0.01)
    # The head's share of the matrix work, the configuration file's note.
    assert 6 * 388_956_160 / matrix == pytest.approx(0.49, abs=0.01)


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
