"""Operations arithmetic that is not `bench/flops.py`, for
bench/tests/test_add_cell.py to add as a file: the dense count of one
GeGLU decoder layer and the head, plus the two operations a logit's softcap
takes where the configuration states one (a further published size, which
reaches this module in `dims`)."""


def train_flops_per_token(m, n_layers, seq):
    d, hd = m["d_model"], m["head_dim"]
    layer = (2 * d * hd * (m["n_heads"] + m["n_kv_heads"]) + 3 * d * m["d_ff"])
    matrix = 2.0 * (n_layers * layer + d * m["vocab_size"])
    attention = n_layers * 4.0 * (seq / 2.0) * m["n_heads"] * hd
    softcap = 2.0 * m["vocab_size"] if m["final_logit_softcap"] else 0.0
    return 3.0 * (matrix + attention + softcap)
