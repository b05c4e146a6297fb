"""Operations a model's forward and backward passes require, counted from its
shapes.  One multiply-accumulate is two FLOPs; the backward pass of a
convolution or matrix product costs twice its forward pass; recomputed work
is never counted; element-wise work (normalisation, activation, softmax,
pooling, the optimizer) is left out, which makes every share of peak computed
from these counts a lower bound of the device's real work.
"""


def conv_macs(h_out, w_out, kh, kw, cin, cout):
    return h_out * w_out * kh * kw * cin * cout


def resnet50_forward_macs(cfg):
    """Multiply-accumulates of one image's forward pass, as the published
    network computes it (a 7x7/2 stem: the s2d fold's zero taps are not model
    work)."""
    size = cfg["image_size"]
    f0 = cfg["num_filters"]
    h = -(-size // 2)                      # stem, stride 2, SAME
    macs = conv_macs(h, h, 7, 7, 3, f0)
    h = -(-h // 2)                         # 3x3/2 max-pool
    cin = f0
    for i, count in enumerate(cfg["stage_sizes"]):
        f = f0 * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            h_out = -(-h // stride)
            macs += conv_macs(h, h, 1, 1, cin, f)            # 1x1
            macs += conv_macs(h_out, h_out, 3, 3, f, f)      # 3x3 (strided)
            macs += conv_macs(h_out, h_out, 1, 1, f, 4 * f)  # 1x1
            if cin != 4 * f or stride != 1:
                macs += conv_macs(h_out, h_out, 1, 1, cin, 4 * f)
            cin, h = 4 * f, h_out
    return macs + cin * cfg["num_classes"]


def resnet50_train_flops(cfg):
    """FLOPs of one optimizer step on one image: forward + backward."""
    return 3 * 2 * resnet50_forward_macs(cfg)


def resnet50_infer_flops(cfg):
    return 2 * resnet50_forward_macs(cfg)


def gpt2_matmul_params(cfg):
    """Parameters that sit in a matrix product of the forward pass: the
    blocks' four matrices and the tied read-out (the embedding look-up and
    the positions are gathers, not products)."""
    d, inner = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    per_layer = d * 3 * d + d * d + 2 * d * inner
    return cfg["n_layer"] * per_layer + cfg["vocab_size"] * d


def gpt2_forward_macs(cfg, seq):
    """Multiply-accumulates of one sequence's forward pass: every matmul
    parameter once a token, plus the attention scores and the weighted sum.
    Causal attention needs half of the S x S products (the masked half is
    not model work): S * (S + 1) / 2 per head and product."""
    d = cfg["n_embd"]
    attn = cfg["n_layer"] * 2 * (seq * (seq + 1) // 2) * d
    return seq * gpt2_matmul_params(cfg) + attn


def gpt2_train_flops(cfg, seq=None):
    """FLOPs of one optimizer step on one sequence (6 N per token plus
    attention, no recomputation)."""
    return 3 * 2 * gpt2_forward_macs(cfg, seq or cfg["n_positions"])


def train_flops_per_example(cfg):
    """By the configuration's ``reference`` family."""
    return {"resnet50": resnet50_train_flops,
            "gpt2": gpt2_train_flops}[cfg["reference"]](cfg)


def infer_flops_per_example(cfg):
    return {"resnet50": resnet50_infer_flops}[cfg["reference"]](cfg)
