"""The bytes the sweeps need per build, checked by hand at the
configuration's own shapes."""

from bench import work


def test_stepwise_sweep_bytes_by_hand():
    # N=10,000 x M=32,768 complex64, k=100: per sweep S is
    # 10,000 * 32,768 * 8 = 2,621,440,000 B, q 80,000 B, acc read and
    # written and |s|^2 read 3 * 4 * 32,768 = 393,216 B, c written
    # 8 * 32,768 = 262,144 B: 2,622,175,360 B, times 100 sweeps.
    assert work.greedy_update_bytes(10_000, 32_768, "complex64", 100) \
        == 262_217_536_000
    # at 819 GB/s that is the 0.32 s floor of a build
    assert abs(262_217_536_000 / 819e9 - 0.3202) < 1e-4


def test_blocked_sweep_bytes_by_hand():
    # p=8: ceil(100 / 8) = 13 sweeps, each S 2,621,440,000 B, the 8 new
    # vectors 640,000 B, acc read and written 262,144 B and the 8 rows of
    # C written 2,097,152 B: 2,624,439,296 B.
    assert work.block_sweep_bytes(10_000, 32_768, "complex64", 100, 8) \
        == 13 * 2_624_439_296


def test_sharded_sweep_counts_one_chips_columns():
    config = {"n_rows": 10_000, "n_cols": 131_072, "dtype": "complex64",
              "max_k": 100}
    one_chip = dict(config, n_cols=32_768)
    traffic = {"block_p": 1}
    assert work.sweep_bytes(config, traffic, 4) \
        == work.sweep_bytes(one_chip, traffic, 1)
