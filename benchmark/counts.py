"""Operations and bytes the algorithm requires, from shapes alone.

A convolution of a kh x kw kernel from cin to cout channels onto an
(oh, ow) output is 2*kh*kw*cin*cout*oh*ow operations (a multiply and an add
per tap). Nothing here knows how the program implements a layer: a
restructured convolution, a recomputed activation or a padded channel adds
no required operation. Training counts forward + backward as three forwards
and no recomputation.

The readers call a count one way, `fn(config, spec)` (benchmark/families.py):
the functions at the end of this file are those, thin adapters that take the
model and the stored pyramid's type from a configuration's file, the sizes
from a workload's file, and leave the arithmetic above as it is.
"""

from __future__ import annotations

from typing import Dict, Tuple


def conv_flops(oh: int, ow: int, kh: int, kw: int, cin: int, cout: int) -> int:
    return 2 * kh * kw * cin * cout * oh * ow


def _ceil_half(n: int) -> int:
    return (n + 1) // 2


def _stride(downsample: int, threshold: int) -> int:
    return 1 + int(downsample > threshold)


def _res_block(h, w, cin, cout, stride) -> Tuple[int, int, int]:
    oh, ow = (h, w) if stride == 1 else (_ceil_half(h), _ceil_half(w))
    flops = conv_flops(oh, ow, 3, 3, cin, cout) + conv_flops(oh, ow, 3, 3, cout, cout)
    if not (stride == 1 and cin == cout):
        flops += conv_flops(oh, ow, 1, 1, cin, cout)
    return flops, oh, ow


def trunk_flops(cfg: Dict, h: int, w: int) -> Tuple[int, int, int]:
    """One image through stem + layer1..3 -> (operations, out_h, out_w)."""
    ds = cfg["n_downsample"]
    if _stride(ds, 2) == 2:
        h, w = _ceil_half(h), _ceil_half(w)
    flops = conv_flops(h, w, 7, 7, 3, 64)
    for cin, cout, stride in (
        (64, 64, 1), (64, 64, 1), (64, 96, _stride(ds, 1)), (96, 96, 1),
        (96, 128, _stride(ds, 0)), (128, 128, 1),
    ):
        f, h, w = _res_block(h, w, cin, cout, stride)
        flops += f
    return flops, h, w


def prelude_flops(cfg: Dict, h: int, w: int) -> int:
    """Both encoders, the context convolutions and the correlation volume
    for one pair."""
    hidden = cfg["hidden_dims"]
    n = cfg["n_gru_layers"]
    trunk, h8, w8 = trunk_flops(cfg, h, w)
    # Shared: both images through one trunk. Otherwise the left image through
    # the context trunk and both through the feature trunk.
    flops = (2 if cfg["shared_backbone"] else 3) * trunk
    if cfg["shared_backbone"]:
        flops += 2 * (_res_block(h8, w8, 128, 128, 1)[0] + conv_flops(h8, w8, 3, 3, 128, 256))
    else:
        flops += 2 * conv_flops(h8, w8, 1, 1, 128, 256)
    sh, sw = h8, w8
    for i in range(n):
        if i > 0:
            f0, sh, sw = _res_block(sh, sw, 128, 128, 2)
            flops += f0 + _res_block(sh, sw, 128, 128, 1)[0]
        width = hidden[2 - i]
        if i < 2:
            flops += 2 * (_res_block(sh, sw, 128, 128, 1)[0] + conv_flops(sh, sw, 3, 3, 128, width))
        else:
            flops += 2 * conv_flops(sh, sw, 3, 3, 128, width)
        flops += conv_flops(sh, sw, 3, 3, 128, 3 * width)
    flops += 2 * h8 * w8 * w8 * 256  # all-pairs correlation along each row
    return flops


def _gru_flops(h, w, width, cin) -> int:
    return 3 * conv_flops(h, w, 3, 3, cin, width)


def lookup_flops(cfg: Dict, h8: int, w8: int) -> int:
    """Two products and an add per interpolated tap."""
    return 3 * cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1) * h8 * w8


def iteration_flops(cfg: Dict, h8: int, w8: int) -> int:
    hidden = cfg["hidden_dims"]
    n = cfg["n_gru_layers"]
    taps = cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1)
    h16, w16 = _ceil_half(h8), _ceil_half(w8)
    h32, w32 = _ceil_half(h16), _ceil_half(w16)
    gru32 = _gru_flops(h32, w32, hidden[0], hidden[0] + hidden[1]) if n == 3 else 0
    gru16 = _gru_flops(h16, w16, hidden[1], hidden[1] + hidden[2] + (hidden[0] if n > 2 else 0)) if n >= 2 else 0
    gru08 = _gru_flops(h8, w8, hidden[2], hidden[2] + 128 + (hidden[1] if n > 1 else 0))
    motion = (
        conv_flops(h8, w8, 1, 1, taps, 64) + conv_flops(h8, w8, 3, 3, 64, 64)
        + conv_flops(h8, w8, 7, 7, 1, 64) + conv_flops(h8, w8, 3, 3, 64, 64)
        + conv_flops(h8, w8, 3, 3, 128, 126)
    )
    head = conv_flops(h8, w8, 3, 3, hidden[2], 256) + conv_flops(h8, w8, 3, 3, 256, 1)
    flops = gru32 + gru16 + gru08 + motion + head + lookup_flops(cfg, h8, w8)
    if cfg["slow_fast_gru"]:
        flops += gru32 + (gru32 + gru16 if n >= 2 else 0)
    return flops


def upsample_flops(cfg: Dict, h8: int, w8: int) -> int:
    factor = 2 ** cfg["n_downsample"]
    mask = conv_flops(h8, w8, 3, 3, cfg["hidden_dims"][2], 256) + conv_flops(h8, w8, 1, 1, 256, 9 * factor * factor)
    return mask + 2 * 9 * factor * factor * h8 * w8


def coarse_hw(cfg: Dict, h: int, w: int) -> Tuple[int, int]:
    _, h8, w8 = trunk_flops(cfg, h, w)
    return h8, w8


def inference_flops(cfg: Dict, h: int, w: int, iters: int) -> int:
    """One disparity map at (h, w), `iters` refinement steps, one upsample."""
    h8, w8 = coarse_hw(cfg, h, w)
    return prelude_flops(cfg, h, w) + iters * iteration_flops(cfg, h8, w8) + upsample_flops(cfg, h8, w8)


def train_sample_flops(cfg: Dict, h: int, w: int, iters: int) -> int:
    """One sample's forward (a prediction upsampled at every step) and
    backward; recomputation is not counted."""
    h8, w8 = coarse_hw(cfg, h, w)
    forward = prelude_flops(cfg, h, w) + iters * (iteration_flops(cfg, h8, w8) + upsample_flops(cfg, h8, w8))
    return 3 * forward


def _pyramid_widths(cfg: Dict, w8: int):
    widths = [w8]
    for _ in range(cfg["corr_levels"] - 1):
        widths.append(widths[-1] // 2)
    return widths


def lookup_bytes(cfg: Dict, h8: int, w8: int, storage_bytes: int, out_bytes: int) -> int:
    """What one lookup has to move for one pair: per query and level the
    2r+2 stored correlations its taps interpolate between, the query's
    coordinate, and the taps it writes. A kernel that streams whole pyramid
    rows moves more than this; no kernel can move less."""
    r = cfg["corr_radius"]
    levels = cfg["corr_levels"]
    per_query = levels * (2 * r + 2) * storage_bytes + 4 + levels * (2 * r + 1) * out_bytes
    return per_query * h8 * w8


def scatter_bytes(cfg: Dict, h8: int, w8: int, grad_bytes: int, out_bytes: int) -> int:
    """The lookup's backward for one pair: the taps' gradients and the
    coordinates read, the pyramid's gradient written once."""
    levels = cfg["corr_levels"]
    r = cfg["corr_radius"]
    read = (levels * (2 * r + 1) * grad_bytes + 4) * h8 * w8
    written = h8 * w8 * sum(_pyramid_widths(cfg, w8)) * out_bytes
    return read + written


# -- what the layer metrics' files name: fn(config, spec) ---------------------

_BYTES = {"bfloat16": 2, "float32": 4}


def _stored_bytes(config: Dict) -> int:
    """Bytes of a stored correlation, of a tap and of a tap's gradient: the
    program keeps the pyramid, and the lookup's output with it, in the
    configuration's `program.corr_dtype`."""
    return _BYTES[config["program"]["corr_dtype"]]


def inference_flops_per_map(config: Dict, spec: Dict) -> int:
    return inference_flops(config["model"], *spec["image_hw"], spec["iters"])


def train_flops_per_sample(config: Dict, spec: Dict) -> int:
    return train_sample_flops(config["model"], *spec["image_hw"], spec["iters"])


def lookup_flops_per_call(config: Dict, spec: Dict) -> int:
    """One lookup (or its backward) of one pair: a call is a pair x an
    iteration."""
    model = config["model"]
    return lookup_flops(model, *coarse_hw(model, *spec["image_hw"]))


def lookup_bytes_per_call(config: Dict, spec: Dict) -> int:
    model, width = config["model"], _stored_bytes(config)
    return lookup_bytes(model, *coarse_hw(model, *spec["image_hw"]), width, width)


def scatter_bytes_per_call(config: Dict, spec: Dict) -> int:
    model, width = config["model"], _stored_bytes(config)
    return scatter_bytes(model, *coarse_hw(model, *spec["image_hw"]), width, width)
