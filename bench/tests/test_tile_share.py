"""``attn_live_tile_share`` against a hand-built window of known tile
counts."""
from bench import harness


def window(steps):
    return harness.Window(
        conf={}, chips=1, device_kind="cpu", setup_s=1.0, window_s=1.0,
        steps=steps, batches={}, spans=[], rss_start_bytes=0,
        rss_peak_bytes=0, plane_bytes=0.0, trace=None)


def test_share_sums_tiles_over_the_window():
    # 20 + 30 live of 2 x 2048 tiles
    read = harness.reader("attn_live_tile_share")
    steps = [{"step": 3, "attn_tiles_live": 20.0, "attn_tiles_total": 2048.0},
             {"step": 4, "attn_tiles_live": 30.0, "attn_tiles_total": 2048.0}]
    assert read(window(steps)) == 100.0 * 50 / 4096


def test_silent_without_tile_counts():
    # a program whose train step counts no tiles
    read = harness.reader("attn_live_tile_share")
    assert read(window([{"step": 3, "loss": 1.0}])) is None
    assert read(window([])) is None
