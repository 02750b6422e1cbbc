"""Model FLOPs of Dom-ST, from the configuration's shapes.

Counted: the multiply-adds of every matrix product and convolution
(2 FLOPs each).  Not counted: elementwise work (activations, the LSTM's
gate arithmetic, the sort), which is a few percent of the total.  A
training sample costs its forward pass three times: the forward, and the
backward's two products per forward product.  Nothing is recomputed.
"""


def forward_flops(c: dict) -> float:
    """One training window (one sample) through the model."""
    P, T, G = c["num_pixels"], c["window_days"], c["num_heads"]
    C, K, H = c["cnn_channels"], c["kernel_size"], c["lstm_hidden"]
    hid = c["pixcon_hidden"]
    pixcon = 2 * P * (4 * hid + hid)
    conv = 2 * T * K * G * ((P // G) * C + C * C)
    lstm, d = 0, G * C
    for _ in range(c["lstm_layers"]):
        lstm += 2 * T * (d + H) * 4 * H
        d = H
    head_in = H + (P if c["use_target_day"] else 0)
    head = 2 * (head_in * c["mlp_hidden"] + c["mlp_hidden"])
    return float(pixcon + conv + lstm + head)


def train_flops_per_sample(c: dict) -> float:
    return 3.0 * forward_flops(c)
