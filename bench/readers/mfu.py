"""Model FLOP/s utilization: tokens/s/chip from the train loop's clock
times the operations a token needs, by the arithmetic the configuration
file names (`"operations"`, a module under bench/ with
`train_flops_per_token(dims, n_layers, seq)`: matrix parameters and causal
attention, recomputation not counted), over the chip's published peak
(bench/peaks.json). spec: {}."""

import flops
import spec as cells


def read(sources, spec):
    client, model = sources.get("client"), sources["model"]
    if not client or model["device"]["platform"] != "tpu":
        return None
    per_token = cells.named_module(model, "operations").train_flops_per_token(
        model["dims"], model["dims"]["n_layers"], model["seq"])
    peak = flops.peaks(model["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * client["tokens_per_s_chip"] * per_token / peak
