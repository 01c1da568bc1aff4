"""Fast checks of the benchmark's pieces that need no engine."""
import numpy as np
import pytest

from bench import check, load, spec
from bench.flops import kernels
from bench.flops import resnet as resnet_flops
from bench.flops import vit as vit_flops
from bench.sut import shares_tau

OPEN = {"loop": "open", "arrival": "poisson", "rate_rps": 150.0,
        "arrival_seed": 5, "senders": 4, "sizes": [1, 2, 5, 8, 13, 21, 32]}
CLOSED = {"loop": "closed", "clients": 3, "sizes": [1, 2, 5, 8]}


def _key(reqs):
    return [(r.n, r.first, r.due) for r in reqs]


def test_arrival_schedule_repeats_from_its_seed():
    a = load.arrival_times(100.0, 5.0, np.random.default_rng(3))
    b = load.arrival_times(100.0, 5.0, np.random.default_rng(3))
    np.testing.assert_array_equal(a, b)
    assert np.all(np.diff(a) > 0) and a[-1] < 5.0
    assert 400 < len(a) < 600


def test_an_unknown_arrival_process_is_refused():
    with pytest.raises(KeyError, match="unknown arrival"):
        load.plan_open(dict(OPEN, arrival="bursty"), 1, 1.0, 64)


def test_open_plan_repeats_and_seeds_change_only_the_order():
    a, b = (load.plan_open(OPEN, 2**31 + 9, 4.0, 64) for _ in range(2))
    assert _key(a) == _key(b)
    c = load.plan_open(OPEN, 12345, 4.0, 64)
    assert [r.due for r in a] == [r.due for r in c]
    assert sorted(r.n for r in a) == sorted(r.n for r in c)
    assert [r.n for r in a] != [r.n for r in c]
    counts = np.bincount([r.n for r in a])[OPEN["sizes"]]
    assert counts.max() - counts.min() <= 1


def test_closed_plan_repeats_and_is_balanced():
    a = load.plan_closed(CLOSED, 7, 40, 64)
    b = load.plan_closed(CLOSED, 7, 40, 64)
    assert [_key(p) for p in a] == [_key(p) for p in b]
    for plan in a:
        counts = np.bincount([r.n for r in plan])[CLOSED["sizes"]]
        assert counts.tolist() == [10, 10, 10, 10]
    assert _key(a[0]) != _key(a[1])


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_request_images_are_views_inside_the_pool(loop):
    pool = np.zeros((40, 2, 2, 3), np.float32)
    if loop == "open":
        reqs = load.plan_open(OPEN, 2**32 + 1, 4.0, len(pool))
    else:
        reqs = sum(load.plan_closed(CLOSED, 2**32 + 1, 40, len(pool)), [])
    for r in reqs:
        x = r.images(pool)
        assert len(x) == r.n and np.shares_memory(x, pool)
        assert x.flags.c_contiguous


def test_shares_tau_hits_the_target_shares():
    rng = np.random.default_rng(0)
    conf = rng.uniform(0.05, 1.0, (4, 2000))
    alpha = rng.uniform(0.2, 0.8, 2000)
    shares = [0.7, 0.15, 0.1, 0.05]
    tau = shares_tau(conf, alpha, shares, 0.3)
    eff = np.clip(tau[None] + 0.3 * alpha[:, None], 0, 1)
    fires = np.concatenate([conf[:-1].T > eff, np.ones((2000, 1), bool)], 1)
    got = np.bincount(np.argmax(fires, 1), minlength=4) / 2000
    np.testing.assert_allclose(got, shares, atol=0.005)


def test_compare_reads_zero_on_the_reference_and_the_gap_of_a_wrong_class():
    rng = np.random.default_rng(1)
    logits = rng.normal(0, 6, (4, 16, 10)).astype(np.float32)
    alpha = rng.uniform(0.2, 0.8, 16)
    tau = np.asarray([0.9, 0.8, 0.7], np.float32)
    ex, pred, conf = check.decide(logits, alpha, tau, 0.3)
    served = {"exit_idx": ex, "pred": pred, "conf": conf, "alpha": alpha}
    assert all(v == 0 for v in check.compare(served, logits, alpha, tau,
                                             0.3).values())
    wrong = dict(served, pred=(pred + 1) % 10)
    rows = np.arange(16)
    at = logits[ex, rows]
    gap = np.max(at.max(-1) - at[rows, (pred + 1) % 10])
    got = check.compare(wrong, logits, alpha, tau, 0.3)
    assert got["pred_gap"] == pytest.approx(gap) and gap > 0
    later = dict(served, exit_idx=np.minimum(ex + 1, 3))
    assert check.compare(later, logits, alpha, tau, 0.3)["gate_violation"] > 0


def test_sample_holds_a_largest_request_and_repeats():
    reqs = [load.Request(n=n, first=0) for n in (1, 2, 32, 5, 8, 32, 13)]
    for r in reqs:
        r.result = {}
    a = check.sample(reqs, 4, 40)
    assert a == check.sample(reqs, 4, 40)
    assert max(r.n for r in a) == 32 and sum(r.n for r in a) >= 40


def _resnet_cfg(program_cfg):
    return {"depths": list(program_cfg.depths), "width": program_cfg.width,
            "img_res": program_cfg.img_res,
            "in_channels": program_cfg.in_channels,
            "n_classes": program_cfg.n_classes,
            "exit_stages": list(program_cfg.exit_stages),
            "small_input": program_cfg.small_input}


def _vit_cfg(program_cfg):
    keys = ("img_res", "patch", "n_layers", "d_model", "n_heads", "d_ff",
            "n_classes", "in_channels", "exit_mlp_ratio")
    return {**{k: getattr(program_cfg, k) for k in keys},
            "exit_layers": list(program_cfg.exit_layers)}


@pytest.mark.parametrize("reduced", [False, True])
def test_resnet_flops_match_the_program_count(reduced):
    """The program counts the first 1x1 convolution of stages 1-3 at the
    stage's output resolution; it runs at the input resolution, four
    times the work.  Everything else agrees exactly."""
    from repro.configs import registry
    from repro.models.resnet import resnet_forward_flops
    get = registry.get_reduced if reduced else registry.get
    pcfg = get("resnet-152")
    cfg = _resnet_cfg(pcfg)
    res = cfg["img_res"] // (1 if pcfg.small_input else 4)
    undercount = 0
    for s in range(1, len(cfg["depths"])):
        res //= 2
        cin, planes = cfg["width"] * 2 ** (s + 1), cfg["width"] * 2 ** s
        undercount += 2 * 3 * res * res * cin * planes
    assert sum(resnet_flops.stage_flops(cfg)) \
        == resnet_forward_flops(pcfg, 1) + undercount
    steps = resnet_flops.exit_flops(cfg)
    assert steps == sorted(steps) and steps[-1] == resnet_flops.step_flops(cfg)


@pytest.mark.parametrize("reduced", [False, True])
def test_vit_flops_match_the_program_count(reduced):
    """The program counts each exit head as one d x classes layer; the
    backbone agrees exactly."""
    from repro.configs import registry
    from repro.models.vit import vit_forward_flops
    get = registry.get_reduced if reduced else registry.get
    pcfg = get("vit-h14")
    cfg = _vit_cfg(pcfg)
    backbone = vit_flops.embed_flops(cfg) \
        + cfg["n_layers"] * vit_flops.layer_flops(cfg)
    heads = pcfg.n_exits * 2 * pcfg.d_model * pcfg.n_classes
    assert backbone == vit_forward_flops(pcfg, 1) - heads
    assert vit_flops.exit_flops(cfg)[-1] == vit_flops.step_flops(cfg)


def test_full_size_exit_shares_of_the_work():
    from repro.configs import registry
    r = _resnet_cfg(registry.get("resnet-152"))
    share = np.asarray(resnet_flops.exit_flops(r)) / resnet_flops.step_flops(r)
    np.testing.assert_allclose(share, [0.068, 0.233, 0.930, 1.0], atol=0.002)


def test_kernel_counts_are_memory_bound_on_v5e():
    peak = spec.peaks("TPU v5 lite")
    for flops, nbytes in (kernels.exit_gate(32, 1000, 2),
                          kernels.difficulty(21, 224, 224, 3)):
        assert nbytes / peak["hbm_bytes_per_s"] \
            > flops / peak["bf16_flops_per_s"]
    assert kernels.difficulty(2, 224, 224, 3)[1] == 2 * (224 * 224 * 3 * 4
                                                         + 512)


def test_an_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")
