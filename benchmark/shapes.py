"""The operations and bytes an algorithm needs for one call, from its
shapes: the numerators of every roofline share.  Kept with the benchmark so
that no later PR computes them another way.  Recomputed operations do not
count; bytes are the least traffic (each tensor written once and read
once), so a share can only be understated by them, never pass 100 %.
"""

from __future__ import annotations


def resnet_convs(cfg: dict) -> list[tuple[int, int, int, int, int]]:
    """(output height, kernel size, channels in, channels out, stride) of
    every convolution of a bottleneck ResNet at ``image_size``."""
    size = cfg["image_size"] // 2
    f = cfg["num_filters"]
    convs = [(size, 7, 3, f, 2)]
    size //= 2                                   # the 3x3/2 max pool
    cin = f
    for stage, count in enumerate(cfg["stage_sizes"]):
        c = f * 2 ** stage
        for b in range(count):
            stride = 2 if stage > 0 and b == 0 else 1
            out = size // stride
            convs += [(size, 1, cin, c, 1), (out, 3, c, c, stride),
                      (out, 1, c, 4 * c, 1)]
            if cin != 4 * c or stride != 1:
                convs.append((out, 1, cin, 4 * c, stride))
            cin, size = 4 * c, out
    return convs


def resnet_train_step(cfg: dict, batch: int) -> dict:
    """One SGD step of a bottleneck ResNet on ``batch`` images: forward,
    and the two backward products of every convolution (3x the forward's
    multiply-adds, 2 operations each)."""
    convs = resnet_convs(cfg)
    width = convs[-1][3]
    macs = sum(h * h * k * k * cin * cout for h, k, cin, cout, _ in convs)
    macs += width * cfg["num_classes"]
    params = sum(k * k * cin * cout + 2 * cout for _, k, cin, cout, _
                 in convs) + (width + 1) * cfg["num_classes"]
    act = sum(h * h * cout for h, _, _, cout, _ in convs)
    image = cfg["image_size"] ** 2 * 3
    # activations in bf16: written and read once going forward, read once
    # and their gradient written and read once going back; the uint8 image
    # read once; float32 parameters read, their momentum read and written,
    # and the parameters written
    nbytes = batch * (act * 2 * 5 + image) + params * 4 * 4
    return {"flops": 2 * 3 * macs * batch, "bytes": nbytes,
            "params": params}


def gpt_params(cfg: dict) -> dict:
    h, layers = cfg["hidden_size"], cfg["num_layers"]
    per_layer = 4 * h * h + 2 * h * cfg["intermediate_size"]
    return {"matmul": layers * per_layer + cfg["vocab_size"] * h,
            "all": layers * (per_layer + 9 * h + cfg["intermediate_size"])
            + (cfg["vocab_size"] + cfg["max_position_embeddings"] + 2) * h}


def gpt_decode_step(cfg: dict, rows: float, live_tokens: float,
                    bytes_per_value: int = 2) -> dict:
    """One decode step over ``rows`` sequences whose contexts hold
    ``live_tokens`` tokens together: every weight and every live K/V entry
    read once."""
    p = gpt_params(cfg)
    kv_per_token = 2 * cfg["num_layers"] * cfg["hidden_size"]
    return {"flops": 2 * p["matmul"] * rows + 2 * 2 * kv_per_token
            * live_tokens,
            "bytes": bytes_per_value * (p["all"] + kv_per_token
                                        * live_tokens)}


def roofline(work: dict, peaks: dict, seconds: float) -> dict:
    """Share of the roofline: the least time the chip could take (the
    larger of operations over peak FLOP/s and bytes over peak bytes/s)
    over the time it took, in per cent, and which of the two bounds; and
    ``mfu``, the operations' side alone: their share of the peak FLOP/s
    over that time, whichever side bounds."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"share": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "least_s": max(t_flops, t_bytes),
            "mfu": 100.0 * t_flops / seconds}
