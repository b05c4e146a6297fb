"""The ``resnet50`` family's count: multiply-accumulates of the published
network from its shapes (``benchmark/flops.py`` has the rules and finds this
file by the configuration's ``reference``)."""


def conv_macs(h_out, w_out, kh, kw, cin, cout):
    return h_out * w_out * kh * kw * cin * cout


def forward_macs(cfg):
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


def train_flops_per_example(cfg):
    """FLOPs of one optimizer step on one image: forward + backward."""
    return 3 * 2 * forward_macs(cfg)


def infer_flops_per_example(cfg):
    return 2 * forward_macs(cfg)
