"""Operations of one ResNet (v2) training step, from the configuration's
shapes alone: multiply-adds of every convolution and of the classifier,
two operations each, forward plus the two backward products (towards the
input and towards the weights).  The first convolution has no input
gradient.  BatchNorm, ReLU, pooling and the optimizer are left out: they
are bound by bytes, not operations, and no recomputation is counted."""


def _out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def forward_macs_per_image(config):
    """Multiply-adds of one image's forward pass, by layer name."""
    filters = config["filter_list"]
    chans, height, width = config["image_shape"]
    macs = {}
    h, w = _out(height, 7, 2, 3), _out(width, 7, 2, 3)
    macs["conv0"] = h * w * filters[0] * chans * 49
    h, w = _out(h, 3, 2, 1), _out(w, 3, 2, 1)
    cin = filters[0]
    for stage, n_units in enumerate(config["units"], 1):
        out = filters[stage]
        mid = out // 4
        for unit in range(1, n_units + 1):
            name = "stage%d_unit%d" % (stage, unit)
            stride = 2 if (unit == 1 and stage > 1) else 1
            macs[name + "_conv1"] = h * w * mid * cin
            h2, w2 = _out(h, 3, stride, 1), _out(w, 3, stride, 1)
            macs[name + "_conv2"] = h2 * w2 * mid * mid * 9
            macs[name + "_conv3"] = h2 * w2 * out * mid
            if unit == 1:
                macs[name + "_sc"] = h2 * w2 * out * cin
            h, w, cin = h2, w2, out
    macs["fc1"] = cin * config["num_classes"]
    return macs


def train_flops_per_image(config):
    """Operations (2 per multiply-add) of forward and backward for one
    image."""
    macs = forward_macs_per_image(config)
    total = sum(macs.values())
    return 2 * (3 * total - macs["conv0"])
