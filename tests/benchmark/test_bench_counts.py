"""counts.py against operations and bytes worked by hand for tiny shapes."""

from benchmark import counts

FULL = dict(hidden_dims=[128, 128, 128], n_gru_layers=3, n_downsample=2, corr_levels=4,
            corr_radius=4, slow_fast_gru=False, shared_backbone=False)
REALTIME = dict(FULL, n_gru_layers=2, n_downsample=3, slow_fast_gru=True, shared_backbone=True)


def test_conv():
    # 3x3, 2 -> 5 channels onto 4x6: 9*2*5 products per pixel, two operations each
    assert counts.conv_flops(4, 6, 3, 3, 2, 5) == 2 * 9 * 2 * 5 * 24 == 4320


def test_trunk_full_on_8x16():
    # stem stride 1 (8x16), layer1 at 8x16, layer2 at 4x8, layer3 at 2x4
    px1, px2, px3 = 8 * 16, 4 * 8, 2 * 4
    stem = 2 * 49 * 3 * 64 * px1
    layer1 = 4 * (2 * 9 * 64 * 64 * px1)
    layer2 = 2 * 9 * 64 * 96 * px2 + 2 * 64 * 96 * px2 + 3 * (2 * 9 * 96 * 96 * px2)
    layer3 = 2 * 9 * 96 * 128 * px3 + 2 * 96 * 128 * px3 + 3 * (2 * 9 * 128 * 128 * px3)
    assert counts.trunk_flops(FULL, 8, 16) == (stem + layer1 + layer2 + layer3, 2, 4)
    assert counts.coarse_hw(FULL, 1984, 2880) == (496, 720)
    assert counts.coarse_hw(REALTIME, 384, 1248) == (48, 156)


def test_iteration_full_on_4x8():
    px8, px16, px32 = 4 * 8, 2 * 4, 1 * 2
    conv3 = lambda px, cin, cout: 2 * 9 * cin * cout * px
    gru32 = 3 * conv3(px32, 256, 128)
    gru16 = 3 * conv3(px16, 384, 128)
    gru08 = 3 * conv3(px8, 384, 128)
    motion = (2 * 36 * 64 * px8 + conv3(px8, 64, 64) + 2 * 49 * 1 * 64 * px8
              + conv3(px8, 64, 64) + conv3(px8, 128, 126))
    head = conv3(px8, 128, 256) + conv3(px8, 256, 1)
    lookup = 3 * 36 * px8
    assert counts.lookup_flops(FULL, 4, 8) == lookup
    assert counts.iteration_flops(FULL, 4, 8) == gru32 + gru16 + gru08 + motion + head + lookup


def test_slow_fast_runs_the_coarse_gru_twice():
    px8, px16 = 4 * 8, 2 * 4
    gru16 = 3 * 2 * 9 * 256 * 128 * px16
    plain = counts.iteration_flops(dict(REALTIME, slow_fast_gru=False), 4, 8)
    assert counts.iteration_flops(REALTIME, 4, 8) == plain + gru16


def test_whole_forward_and_train_sample():
    h, w, iters = 32, 64, 5
    h8, w8 = counts.coarse_hw(FULL, h, w)
    assert (h8, w8) == (8, 16)
    forward = (counts.prelude_flops(FULL, h, w) + iters * counts.iteration_flops(FULL, h8, w8)
               + counts.upsample_flops(FULL, h8, w8))
    assert counts.inference_flops(FULL, h, w, iters) == forward
    train = counts.prelude_flops(FULL, h, w) + iters * (
        counts.iteration_flops(FULL, h8, w8) + counts.upsample_flops(FULL, h8, w8))
    assert counts.train_sample_flops(FULL, h, w, iters) == 3 * train
    # the correlation volume: every pair of positions along a row, 256 features
    assert counts.prelude_flops(FULL, h, w) > 2 * h8 * w8 * w8 * 256
    # upsample: 3x3 128->256, 1x1 256->144, nine weighted taps to each fine pixel
    px = h8 * w8
    assert counts.upsample_flops(FULL, h8, w8) == 2 * 9 * 128 * 256 * px + 2 * 256 * 144 * px + 2 * 9 * 16 * px


def test_lookup_and_scatter_bytes():
    # per query: 4 levels x 10 stored values x 2 bytes, a 4-byte coordinate,
    # 36 taps written at 2 bytes
    assert counts.lookup_bytes(FULL, 4, 8, 2, 2) == (4 * 10 * 2 + 4 + 36 * 2) * 32
    # backward: 36 tap gradients at 2 bytes + the coordinate read; the
    # pyramid's gradient (widths 8, 4, 2, 1) written once at 2 bytes
    assert counts.scatter_bytes(FULL, 4, 8, 2, 2) == (36 * 2 + 4) * 32 + 32 * (8 + 4 + 2 + 1) * 2
