"""Model FLOP counts against a count by hand at a small size."""
from bench.lib import spec


def test_odenet_flops_by_hand():
    mod = spec.model_module("odenet-mnist")
    cfg = {"image": [12, 12, 1], "channels": 2, "classes": 5}
    traffic = {"batch": 2, "method": "rk4", "n_steps": 3}
    # sides: 12 -> 10 (3x3 unpadded) -> 5 -> 2 (4x4, stride 2, pad 1)
    conv1 = 2 * (2 * 2 * 100 * 9 * 1 * 2)  # forward and weight gradient
    conv23 = 3 * 2 * 2 * (25 + 4) * 16 * 2 * 2
    one_conv = 2 * 2 * 4 * 9 * 3 * 2    # 3x3, (2+1) -> 2 channels on 2x2
    f_eval = 2 * one_conv
    head = 3 * 2 * 2 * 2 * 5
    by_hand = conv1 + conv23 + 3 * (4 * 3) * f_eval + head
    assert mod.flops_per_step(cfg, traffic) == by_hand


def test_cnf_flops_by_hand():
    mod = spec.model_module("cnf-miniboone")
    cfg = {"dim": 3, "nhidden": 2, "hdim_factor": 2}
    traffic = {"batch": 5, "method": "rk4", "n_steps": 2}
    f = 2 * 5 * (3 * 6 + 6 * 6 + 6 * 3)  # 3 -> 6 -> 6 -> 3
    aug = 2 * f                          # f and its input VJP
    assert mod.flops_per_step(cfg, traffic) == 3 * (4 * 2) * aug


def test_full_size_counts():
    """The cells' counts at their sizes, as PERF.md quotes them."""
    clf = spec.model_module("odenet-mnist")
    cfg = spec.config("odenet-mnist")
    t = spec.traffic("clf-b128-rk4x8-pnode")
    per_f = 2 * (2 * 128 * 6 * 6 * 9 * 65 * 64)
    assert per_f == 690_094_080
    assert clf.flops_per_step(cfg, t) == 76_766_871_552
    cnf = spec.model_module("cnf-miniboone")
    f = 2 * 1000 * (43 * 860 + 860 * 860 + 860 * 43)
    assert f == 1_627_120_000
    assert cnf.flops_per_step(spec.config("cnf-miniboone"),
                              spec.traffic("cnf-b1000-rk4x8-hutch-pnode")) \
        == 3 * 32 * 2 * f
