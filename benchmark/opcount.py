"""Operations and bytes one step of the algorithm needs, from shapes.

Kept with the benchmark so that no later change to the program can move
the denominator of a utilization. These count what the algorithm
requires, not what one implementation issues: recomputed operations do
not count, and the bytes are those of the tensors a convolution or a
matrix product has to read and write, with everything elementwise
(BatchNorm, ReLU6, casts) assumed fused into its producer.

``causal_lm_train_step`` is copied from ``scripts/bench_lm.py``
(``analytic_train_flops``); ``mobilenetv2_train_step`` replaces the
jaxpr walk of ``bench.py`` (``_conv_dot_traffic``) by the same sum taken
over the published layer table, so it needs no trace of the program.
"""

from __future__ import annotations


def causal_lm_train_step(cfg: dict, batch: int, seq: int,
                         n_layer: int) -> dict:
    """Matrix-product FLOPs of one train step (forward + 2x backward),
    causal attention at half the full-score cost, head included."""
    c, vocab = cfg["n_embd"], cfg["vocab_size"]
    per_block = (8 + 4 * 4.0) * batch * seq * c * c      # qkv+out+4x mlp
    attn = 2 * batch * seq * seq * c                     # scores+values, causal
    head = 2 * batch * seq * c * vocab
    return {"flops": 3.0 * (n_layer * (per_block + attn) + head),
            "bytes": 0.0}


def _divisible(v, d=8):
    new = max(d, int(v + d / 2) // d * d)
    return new + d if new < 0.9 * v else new


def mobilenetv2_convs(cfg: dict):
    """[(k, cin, cout, groups, h_in, stride)] for every convolution of
    the published layer table at the configuration's image size."""
    wm, h = cfg["width_mult"], cfg["image_size"]
    stem = _divisible(32 * wm)
    out = [(3, 3, stem, 1, h, 2)]
    h, cin = h // 2, stem
    for t, c, n, s in cfg["inverted_residual_setting"]:
        cout = _divisible(c * wm)
        for i in range(n):
            stride = s if i == 0 else 1
            mid = cin * t
            if t != 1:
                out.append((1, cin, mid, 1, h, 1))
            out.append((3, mid, mid, mid, h, stride))
            h = h // stride
            out.append((1, mid, cout, 1, h, 1))
            cin = cout
    out.append((1, cin, _divisible(cfg["last_channel"] * max(1.0, wm)), 1,
                h, 1))
    return out


def mobilenetv2_train_step(cfg: dict, batch: int, act_bytes: int = 2) -> dict:
    """FLOPs and materialised-tensor bytes of one train step. Each
    convolution is counted three times (forward, input gradient, weight
    gradient), each time reading two of {input, weights, output} and
    writing the third; activations in the compute type (2 bytes),
    the classifier likewise."""
    flops = bytes_ = 0.0
    for k, cin, cout, groups, h, stride in mobilenetv2_convs(cfg):
        ho = h // stride
        f = 2.0 * k * k * (cin // groups) * cout * ho * ho * batch
        io = (batch * h * h * cin + batch * ho * ho * cout
              + k * k * (cin // groups) * cout) * act_bytes
        flops += 3.0 * f
        bytes_ += 3.0 * io
    head = _divisible(cfg["last_channel"] * max(1.0, cfg["width_mult"]))
    flops += 3.0 * 2.0 * batch * head * cfg["num_classes"]
    return {"flops": flops, "bytes": bytes_}


OPCOUNTS = {"causal_lm_train_step": causal_lm_train_step,
            "mobilenetv2_train_step": mobilenetv2_train_step}
