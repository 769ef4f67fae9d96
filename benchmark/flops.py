"""Operations and bytes the algorithms need, computed from shapes. The
yardstick of ``train_mfu`` and of every ``<kernel>_roofline``; kept with the
benchmark so that no PR that claims a gain can change it."""
import json


# ------------------------------------------------------------- training --
def symbol_macs(symbol, **input_shapes):
    """Multiply-accumulates of ONE forward pass of an mxnet_tpu symbol at
    the given input shapes, from its Convolution and FullyConnected nodes:
    conv = N*Ho*Wo*Cout*(Cin/groups)*kh*kw, fc = N*out*in. Everything else
    (BN, ReLU, pooling, softmax) is left out, as is the convention for
    model FLOPs."""
    internals = symbol.get_internals()
    _args, out_shapes, _aux = internals.infer_shape(**input_shapes)
    shape_of = dict(zip(internals.list_outputs(), out_shapes))
    arg_shapes = dict(zip(symbol.list_arguments(),
                          symbol.infer_shape(**input_shapes)[0]))
    total = 0
    for node in json.loads(symbol.tojson())["nodes"]:
        op, name = node["op"], node["name"]
        if op not in ("Convolution", "FullyConnected"):
            continue
        weight = arg_shapes[name + "_weight"]
        out = shape_of[name + "_output"]
        per_output = 1
        for d in weight[1:]:
            per_output *= d
        n_out = 1
        for d in out:
            n_out *= d
        # conv weight is (Cout, Cin/g, kh, kw) in either layout's argument
        # order; fc weight is (out, in). One MAC per weight element feeding
        # an output element.
        total += n_out * per_output
    return total


def train_flops(macs_forward):
    """Forward + backward FLOPs: 2 per MAC forward, and the backward pass
    costs two forward passes (gradients to data and to weights).
    Recomputation does not count."""
    return 6 * macs_forward


def mfu(units_per_s, flops_per_unit, chips, peak_flops_per_s):
    """Model FLOP/s utilization in %. An end-to-end utilization: it says
    nothing about idle time and is no kernel's roofline share."""
    return 100.0 * units_per_s * flops_per_unit / (chips * peak_flops_per_s)


# -------------------------------------------------------------- serving --
def lm_matmul_params(cfg):
    """Weights that take part in a matrix multiplication per token:
    per layer qkv (3m*m) + out (m*m) + ffn (2*m*f), plus the lm_head."""
    m, f = cfg["model_dim"], cfg["ffn_dim"]
    return cfg["num_layers"] * (4 * m * m + 2 * m * f) + cfg["vocab"] * m


def lm_prefill_flops(cfg, seq):
    """FLOPs of one prefill over ``seq`` positions: 2 per weight per
    position (lm_head on one position only), plus causal attention
    (QK^T and PV: 2 * 2 * seq^2/2 * m per layer)."""
    m = cfg["model_dim"]
    body = cfg["num_layers"] * (4 * m * m + 2 * m * cfg["ffn_dim"])
    return (2 * seq * body + 2 * cfg["vocab"] * m
            + cfg["num_layers"] * 2 * seq * seq * m)


def lm_decode_flops(cfg, batch, context_tokens):
    """FLOPs of one decode step of ``batch`` streams holding
    ``context_tokens`` cached positions in total."""
    m = cfg["model_dim"]
    return (2 * batch * lm_matmul_params(cfg)
            + cfg["num_layers"] * 4 * context_tokens * m)


def flash_fwd_cost(heads, seq, head_dim, itemsize=4):
    """(flops, bytes) one causal flash-attention forward call needs:
    QK^T and PV over the lower triangle; reads Q, K, V and writes O once."""
    flops = 2 * 2 * heads * (seq * seq / 2.0) * head_dim
    nbytes = 4 * heads * seq * head_dim * itemsize
    return flops, nbytes


def paged_attn_cost(heads, head_dim, context_tokens, batch, itemsize=4):
    """(flops, bytes) one paged-attention decode call needs: each stream
    reads its own K and V once (context_tokens positions in total) and does
    QK^T and PV against them."""
    flops = 2 * 2 * heads * head_dim * context_tokens
    nbytes = (2 * context_tokens * heads * head_dim * itemsize
              + 2 * batch * heads * head_dim * itemsize)
    return flops, nbytes


def roofline_share(flops, nbytes, seconds, peak):
    """(share in %, which bound) of the least time the chip could take —
    the larger of flops/peak-FLOP/s and bytes/peak-bytes/s — over the time
    it took."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
