"""Operations and bytes from shapes: the benchmark's own arithmetic for
`train_mfu`, `serve_mfu` and `conv_roofline` (copied in spirit from
mxnet_tpu/utils/flops.py: 2 FLOPs a multiply-add in the matmul-class
operations, a training step = 3 x forward)."""

def conv_cost(n, h_out, w_out, c_in, c_out, kh, kw, h_in, w_in,
              itemsize=2, needs_dgrad=True):
    """One convolution of a training step: FLOPs and least HBM bytes of
    its forward, data-gradient and weight-gradient kernels (each reads
    two of {x, w, y} and writes the third, once, at `itemsize`)."""
    fwd = 2.0 * n * h_out * w_out * c_out * kh * kw * c_in
    x = n * h_in * w_in * c_in * itemsize
    w = c_out * kh * kw * c_in * itemsize
    y = n * h_out * w_out * c_out * itemsize
    kernels = [("fwd", fwd, x + w + y), ("wgrad", fwd, x + y + w)]
    if needs_dgrad:
        kernels.append(("dgrad", fwd, y + w + x))
    return {"forward_flops": fwd, "kernels": kernels}


def resnet_nodes(cfg, batch):
    """{node name: cost} of every Convolution and the FullyConnected of
    the ResNet the configuration states, named as models/resnet.py
    names them (the executor's named_scope carries those names into the
    device trace)."""
    units, filters, bottle = cfg["units"], cfg["filters"], cfg["bottleneck"]
    side = int(cfg["image_size"])
    out = {}
    h = side // 2
    out["conv0"] = conv_cost(batch, h, h, 3, filters[0], 7, 7, side, side,
                             needs_dgrad=False)
    h = h // 2                      # 3x3/2 max pool
    c = filters[0]
    for i, n_units in enumerate(units):
        f = filters[i + 1]
        for j in range(n_units):
            name = f"stage{i + 1}_unit{j + 1}"
            stride = 2 if (i > 0 and j == 0) else 1
            ho = h // stride
            if bottle:
                m = f // 4
                out[name + "_conv1"] = conv_cost(batch, h, h, c, m, 1, 1,
                                                 h, h)
                out[name + "_conv2"] = conv_cost(batch, ho, ho, m, m, 3, 3,
                                                 h, h)
                out[name + "_conv3"] = conv_cost(batch, ho, ho, m, f, 1, 1,
                                                 ho, ho)
            else:
                out[name + "_conv1"] = conv_cost(batch, ho, ho, c, f, 3, 3,
                                                 h, h)
                out[name + "_conv2"] = conv_cost(batch, ho, ho, f, f, 3, 3,
                                                 ho, ho)
            if j == 0:
                out[name + "_sc"] = conv_cost(batch, ho, ho, c, f, 1, 1,
                                              h, h)
            h, c = ho, f
    out["fc1"] = conv_cost(batch, 1, 1, c, int(cfg["num_classes"]), 1, 1,
                           1, 1)
    return out


def resnet_forward_flops_per_image(cfg):
    return sum(v["forward_flops"] for v in resnet_nodes(cfg, 1).values())


def resnet_train_flops_per_image(cfg):
    """The analytic convention: a training step is 3 x forward."""
    return 3.0 * resnet_forward_flops_per_image(cfg)


def roofline_seconds(kernels, peaks):
    """Least seconds for a list of (name, flops, bytes) kernels, and how
    many of them compute bounds and how many memory bounds."""
    total, by = 0.0, {"compute": 0, "memory": 0}
    for _name, flops, nbytes in kernels:
        tc = flops / peaks["bf16_flops_per_s"]
        tm = nbytes / peaks["hbm_bytes_per_s"]
        total += max(tc, tm)
        by["compute" if tc >= tm else "memory"] += 1
    return total, by


def decoder_matmul_params(cfg):
    """Weights a token passes through as matrix multiplications: the
    four attention projections and the two MLP matrices of every layer,
    and the tied output projection (the embedding lookup and the learned
    positions are gathers, not matmuls)."""
    d, ff = int(cfg["hidden_size"]), int(cfg["ffn_dim"])
    per_layer = 4 * d * d + 2 * d * ff
    return int(cfg["num_hidden_layers"]) * per_layer \
        + int(cfg["vocab_size"]) * d


def decoder_total_params(cfg):
    d = int(cfg["hidden_size"])
    norms = (2 * int(cfg["num_hidden_layers"]) + 1) * d
    return decoder_matmul_params(cfg) \
        + int(cfg["max_position_embeddings"]) * d + norms


def decoder_flops_per_token(cfg):
    """2 x matmul parameters: the usual serving convention, attention's
    own score and value products left out (they grow with context and
    are under a tenth at these lengths)."""
    return 2.0 * decoder_matmul_params(cfg)
