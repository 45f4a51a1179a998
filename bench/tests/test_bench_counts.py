"""The yardstick's counts: model FLOPs per token against a hand count from
the program's own parameter shapes, Eq. (4)'s least bytes against m * D,
and the peak table's refusal of an unknown chip."""
import json
import math
from pathlib import Path

import jax
import pytest

from bench import counts, peaks
from bench.refs import moe, xlstm

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def sizes(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def xlstm_sizes():
    """The repository's xlstm-125m at 16 blocks in the 7:1 ratio (no cell
    holds it: PERF.md, Open questions)."""
    import dataclasses
    from repro.configs import get_config
    replace = {"num_layers": 16, "slstm_every": 8}
    cfg = dataclasses.replace(get_config("xlstm-125m"), **replace)
    return {"base": "xlstm-125m", "replace": replace,
            "sizes": dataclasses.asdict(cfg)}


def abstract(conf):
    import dataclasses
    from repro.configs import get_config
    from repro.models import build_model
    cfg = dataclasses.replace(get_config(conf["base"]), **conf["replace"])
    return cfg, build_model(cfg).abstract()


def leaf(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree.shape


def test_xlstm_flops_per_token_match_shapes():
    conf = xlstm_sizes()
    cfg, p = abstract(conf)
    s = conf["sizes"]
    size = lambda path: math.prod(leaf(p, path))
    matmul = sum(size(f"mlstm/{w}") for w in
                 ("w_gate", "w_q", "w_k", "w_v", "w_i", "w_f", "w_down"))
    matmul += size("slstm/w_gates") + size("slstm/w_down")
    matmul += s["vocab_size"] * s["d_model"]     # tied output layer
    n_m, _, din = leaf(p, "mlstm/w_q")
    H = s["num_heads"]
    P = din // H
    n_s, _, Ps, _ = leaf(p, "slstm/r_gates")
    # sLSTM recurrence: models.xlstm.slstm_flops_correction per token
    from repro.models.xlstm import slstm_flops_correction
    slstm = slstm_flops_correction(cfg, 1, 1)
    fwd = 2 * matmul + n_m * H * (4 * P * P + 4 * P) + slstm
    assert xlstm.flops_per_token(s, 1024) == pytest.approx(3 * fwd)
    assert n_s * 2 * H * Ps * 4 * Ps == slstm


def test_moe_flops_per_token_match_shapes():
    conf = sizes("granite-moe-1b-a400m-1chip")
    _, p = abstract(conf)
    s = conf["sizes"]
    L, E, d, ff = leaf(p, "layers/moe/w_gate")
    size = lambda path: math.prod(leaf(p, path))
    attn = sum(size(f"layers/{w}") for w in ("wq", "wk", "wv", "wo"))
    active = size("layers/moe/router") + \
        3 * size("layers/moe/w_gate") * s["num_experts_per_tok"] // E
    matmul = attn + active + s["vocab_size"] * d
    _, _, H, hd = leaf(p, "layers/wq")
    S = 1024
    fwd = 2 * matmul + L * 2 * H * hd * (S + 1)
    assert moe.flops_per_token(s, S) == pytest.approx(3 * fwd)


@pytest.mark.parametrize("name", ["xlstm-125m", "granite-moe-1b-a400m-1chip"])
def test_update_bytes_are_three_passes_over_m_by_d(name):
    _, p = abstract(xlstm_sizes() if name == "xlstm-125m" else sizes(name))
    D = sum(math.prod(a.shape) for a in jax.tree.leaves(p))
    assert counts.params_per_agent(p) == D
    assert counts.update_min_bytes(4, D, 2) == 3 * 4 * D * 2
    assert counts.update_min_seconds(4, D, 2, 819e9) == \
        pytest.approx(24 * D / 819e9)


def test_peaks_refuse_an_unknown_chip():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")


def test_update_yardstick_counts_each_chips_share():
    D = 211_393_536
    one = 3 * 4 * D * 2 / 819e9
    assert counts.update_min_seconds(4, D, 2, 819e9, chips=1) == one
    assert counts.update_min_seconds(4, D, 2, 819e9) == one
    assert counts.update_min_seconds(4, D, 2, 819e9, chips=4) == one / 4
    assert counts.update_min_bytes(2, D, 2, chips=4) == 3 * 2 * D * 2 / 4
