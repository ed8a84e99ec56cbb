"""Least HBM bytes of one LANS update, from a configuration alone.

The parameter shapes follow from the configuration's `model` widths, and
the bytes a parameter costs from its `precision`: under `bf16` a matrix or
embedding is a bfloat16 model copy with a float32 master, and a norm
parameter or bias stays float32 with no master (the configurations'
`precision_policy`); both moments are bfloat16. Per parameter, one pass:

- with a master: read the float32 gradient (4), the master (4) and both
  moments (2 + 2); write the master (4), both moments (2 + 2) and the
  bfloat16 model copy (2): 22 bytes;
- float32 with no master: the same without the copy, 20 bytes.

Nothing here reads the program, so the count stays the same whatever
implements LANS; the norms LANS takes per block, and any second pass,
count nothing.
"""

BYTES = {"bf16": {"master": 22, "fp32": 20}}


def bert_params(model: dict) -> tuple:
    """(matrix and embedding parameters, norm and bias parameters)."""
    L, d, f = model["n_layers"], model["d_model"], model["d_ff"]
    V, P, T = model["vocab"], model["max_pos"], model["type_vocab"]
    matrices = ((V + P + T) * d + L * (4 * d * d + 2 * d * f)
                + d * d + d * d + 2 * d)   # MLM transform, pooler, NSP
    # embedding LN; per layer q, k, v biases (the output projection has
    # none, as bench/reference/bert.py runs it), 2 LNs, 2 MLP biases; MLM
    # transform bias and LN, output bias; pooler and NSP biases
    vectors = 2 * d + L * (8 * d + f) + 3 * d + V + d + 2
    return matrices, vectors


def decoder_params(model: dict) -> tuple:
    """(matrix and embedding parameters, norm parameters)."""
    L, d, f, V = model["n_layers"], model["d_model"], model["d_ff"], model["vocab"]
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    mats = 3 if model.get("gated_mlp", True) else 2
    E = model.get("n_experts", 0)
    ffn = d * E + E * mats * d * f if E else mats * d * f
    head = 0 if model.get("tie_embeddings", True) else d * V
    matrices = V * d + head + L * (2 * d * h * hd + 2 * d * kv * hd + ffn)
    return matrices, d + L * 2 * d         # final norm; two norms a layer


def least_bytes(config: dict) -> int:
    matrices, vectors = {"bert": bert_params,
                         "decoder": decoder_params}[config["kind"]](
                             config["model"])
    cost = BYTES[config["precision"]]
    return matrices * cost["master"] + vectors * cost["fp32"]
