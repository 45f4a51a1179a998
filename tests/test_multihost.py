"""End-to-end tests for the multi-controller deployment
(`repro.launch.multihost`).

The two acceptance properties of the PR live here: (1) a multi-process
run is bit-identical — final params AND merged wire stream — to the
single-process run of the same driver, and (2) a SIGKILLed rank leaves
survivors on a doubly stochastic overlay coupling, and a subsequent
``--resume`` rolls every shard back to the quorum step, bumps the Λ-key
generation, and completes finite.  The shard audit proves no key
material and no foreign rows ever land in a rank's checkpoint shard.
"""
import json
import os

import numpy as np
import pytest

from repro.checkpoint import io as ckpt_io
from repro.launch import multihost as mh

ARCH = "stablelm-3b-tiny"


def _args(extra, root=None):
    argv = ["--arch", ARCH, "--agents", "4", "--steps", "4",
            "--per-agent-batch", "2", "--seq-len", "16", "--seed", "0",
            "--checkpoint-every", "2", "--timeout", "60"]
    if root:
        argv += ["--checkpoint-dir", root]
    a = mh.build_multihost_parser().parse_args(argv + extra)
    return a


def _shard_arrays(host_dir, step):
    """Arrays stored in one shard step dir, keyed by their tree path
    (e.g. "['x']") via tree.json — shape-agnostic read."""
    d = os.path.join(host_dir, ckpt_io.step_dirname(step))
    tree = json.load(open(os.path.join(d, "tree.json")))
    out = {}
    with np.load(os.path.join(d, "arrays.npz")) as z:
        for i, path in enumerate(tree["paths"]):
            out[path] = z[f"a{i}"]
    return out


def _load_x(root, world, step):
    rows = [_shard_arrays(mh.host_dir(root, r), step)["['x']"]
            for r in range(world)]
    return np.concatenate(rows)


@pytest.fixture(scope="module")
def world_runs(tmp_path_factory):
    """One world=1 and one world=2 run of the same configuration, both
    with wiretap capture — shared by the bit-identity and audit tests."""
    r1 = str(tmp_path_factory.mktemp("mh_w1"))
    r2 = str(tmp_path_factory.mktemp("mh_w2"))
    o1 = mh.launch(_args(["--world", "1", "--wiretap"], r1))
    o2 = mh.launch(_args(["--world", "2", "--wiretap"], r2))
    return r1, o1, r2, o2


def test_world2_bit_identical_to_world1(world_runs):
    r1, o1, r2, o2 = world_runs
    assert o1["ok"] and o2["ok"]
    assert o2["casualties"] == []
    x1 = _load_x(r1, 1, 4)
    x2 = _load_x(r2, 2, 4)
    assert x1.shape[0] == 4 and x2.shape == x1.shape
    assert np.array_equal(x1, x2)
    with np.load(os.path.join(r1, "wiretap_merged.npz")) as z1, \
            np.load(os.path.join(r2, "wiretap_merged.npz")) as z2:
        assert list(z1["steps"]) == list(z2["steps"])
        assert np.array_equal(z1["v"], z2["v"])


def test_shard_holds_only_local_rows_and_no_key_material(world_runs):
    """Key-locality audit: a rank's shard contains exactly its own (L, D)
    x block and the step scalar — no PRNG keys, no Λ draws, no other
    rank's rows, and the spanning manifest records the layout."""
    _, _, r2, _ = world_runs
    for r in range(2):
        arrs = _shard_arrays(mh.host_dir(r2, r), 4)
        assert set(arrs) == {"['x']", "['step']"}
        assert arrs["['x']"].shape[0] == 2  # L = agents/world, never m
        assert arrs["['x']"].dtype == np.float32
    man = mh.read_manifest(r2)
    assert man["world"] == 2 and man["per_rank"] == 2
    assert man["hosts"] == ["host_0", "host_1"]
    assert man["transport"] == "socket"
    # wiretaps store only the v tensor + step ids (sender-side columns)
    for r in range(2):
        with np.load(os.path.join(mh.host_dir(r2, r), "wiretap.npz")) as z:
            assert set(z.files) == {"v", "steps"}


def test_kill_rank_then_resume_completes(tmp_path):
    """SIGKILL rank 1 mid-run: survivors finish finite on the overlay
    coupling (fault log pins its double stochasticity); ``--resume``
    rolls back to the quorum, bumps the Λ generation, and completes."""
    root = str(tmp_path / "mh_chaos")
    o1 = mh.launch(_args(["--world", "2", "--steps", "6",
                          "--chaos-kill-rank", "1",
                          "--chaos-kill-step", "3",
                          "--timeout", "20"], root))
    assert o1["ok"] and o1["casualties"] == [1]
    # the survivor recorded the overlay event with stochasticity errors
    log = json.load(open(os.path.join(mh.host_dir(root, 0),
                                      "fault_log.json")))
    assert log["events"], "survivor never recorded the dead set"
    ev = log["events"][0]
    assert ev["dead"] == [2, 3]  # rank 1 owned agents 2..3
    assert ev["row_sum_err"] < 1e-6 and ev["col_sum_err"] < 1e-6
    # quorum: rank 1 died at step 3 -> its newest durable step is 2
    assert mh.quorum_step(root, 2) == 2
    o2 = mh.launch(_args(["--world", "2", "--steps", "6", "--resume",
                          "--timeout", "20"], root))
    assert o2["ok"] and o2["casualties"] == []
    assert o2["generation"] == 1  # fresh Λ draws from the quorum forward
    for r in range(2):
        s = o2["ranks"][str(r)]
        assert s is not None and s["finite"] and s["final_step"] == 6
    x = _load_x(root, 2, 6)
    assert np.isfinite(x).all()


def test_pipelined_transport_bit_matches_blocking(tmp_path):
    """--frames-ahead > 0 swaps in PipelinedSocketTransport; the final
    shard digests must equal the blocking run's exactly (the paper's
    recursion is synchronous — the pipeline only moves WORK off the
    critical path, never reorders the math), and every rank exports the
    comm counter block to its summary and fault_log.json."""
    rb = str(tmp_path / "mh_blk")
    rp = str(tmp_path / "mh_pipe")
    ob = mh.launch(_args(["--world", "2"], rb))
    op = mh.launch(_args(["--world", "2", "--frames-ahead", "2",
                          "--outbox-frames", "8"], rp))
    assert ob["ok"] and op["ok"]
    for r in range(2):
        sb, sp = ob["ranks"][str(r)], op["ranks"][str(r)]
        assert sp["x_sha256"] == sb["x_sha256"]
        assert sb["comm"]["transport"] == "SocketTransport"
        assert sp["comm"]["transport"] == "PipelinedSocketTransport"
        for s in (sb, sp):
            assert s["comm"]["drops"] == 0
            assert s["comm"]["tag_failures"] == 0
            assert s["comm"]["comm_wait_s"] >= 0.0
        log = json.load(open(os.path.join(mh.host_dir(rp, r),
                                          "fault_log.json")))
        assert log["events"] == []
        assert log["comm"]["transport"] == "PipelinedSocketTransport"
    assert np.array_equal(_load_x(rb, 2, 4), _load_x(rp, 2, 4))


def test_quorum_step_intersects_shards(tmp_path):
    root = str(tmp_path)
    like = {"x": np.zeros((1, 3), np.float32)}
    for r, steps in ((0, [2, 4, 6]), (1, [2, 4])):
        for s in steps:
            ckpt_io.save_checkpoint(mh.host_dir(root, r), s, like)
    assert mh.quorum_step(root, 2) == 4
    assert mh.quorum_step(root, 3) is None  # host_2 has nothing


def test_generation_counter(tmp_path):
    root = str(tmp_path)
    assert mh.next_generation(root, resume=False) == 0
    assert mh.next_generation(root, resume=True) == 0  # no manifest yet
    ckpt_io._atomic_write_json(os.path.join(root, mh.MANIFEST),
                               {"generation": 0, "casualties": [1]})
    assert mh.next_generation(root, resume=True) == 1
    assert mh.next_generation(root, resume=False) == 0  # fresh run resets
    ckpt_io._atomic_write_json(os.path.join(root, mh.MANIFEST),
                               {"generation": 3, "casualties": []})
    assert mh.next_generation(root, resume=True) == 3  # clean resume keeps


def test_resume_refuses_foreign_fingerprint(tmp_path):
    root = str(tmp_path / "mh_fp")
    out = mh.launch(_args(["--world", "1"], root))
    assert out["ok"]
    with pytest.raises(ValueError, match="topology"):
        mh.run_rank(_args(["--world", "1", "--resume",
                           "--topology", "complete"], root))
    a = _args(["--world", "1", "--resume"], root)
    a.seed = 1  # same shards, different deployment identity
    with pytest.raises(ValueError, match="deployment"):
        mh.run_rank(a)


def test_resume_without_any_shard_refuses(tmp_path):
    with pytest.raises(FileNotFoundError, match="resume"):
        mh.run_rank(_args(["--world", "1", "--resume"],
                          str(tmp_path / "empty")))


def test_agents_must_split_over_world():
    with pytest.raises(ValueError, match="split"):
        mh.launch(_args(["--world", "3"], None))


def test_tpu_ranks_refused_beyond_one_per_host(monkeypatch):
    """A rank process opens every chip of its host, so the launcher
    refuses a second TPU-using rank before spawning any; CPU ranks (read
    from JAX_PLATFORMS without a probe) pass."""
    assert mh.rank_platform({"JAX_PLATFORMS": "cpu"}) == "cpu"
    mh.check_rank_platform(4, {"JAX_PLATFORMS": "cpu"})
    monkeypatch.setattr(mh, "rank_platform", lambda env: "tpu")
    mh.check_rank_platform(1, {})
    with pytest.raises(ValueError, match="TPU-using rank"):
        mh.check_rank_platform(2, {})
    with pytest.raises(ValueError, match="TPU-using rank"):
        mh.launch(_args(["--world", "2"], None))


def test_validate_agent_tiling_errors():
    """Satellite: `launch.mesh.validate_agent_tiling` refuses bad agent
    tilings with the fitting counts spelled out."""
    from repro.launch.mesh import validate_agent_tiling

    class FakeMesh:
        shape = {"pod": 2, "data": 4, "model": 1}

    assert validate_agent_tiling(FakeMesh(), 8) == 1
    assert validate_agent_tiling(FakeMesh(), 16) == 2
    with pytest.raises(ValueError, match="multiple of 8"):
        validate_agent_tiling(FakeMesh(), 6)
    with pytest.raises(ValueError, match="positive"):
        validate_agent_tiling(FakeMesh(), 0)


def test_make_global_mesh_single_process():
    """On this container (1 device, 1 process) the global mesh is the
    flat ("data", "model") layout and bad model_parallel is refused."""
    from repro.launch.mesh import make_global_mesh, num_agents
    mesh = make_global_mesh()
    assert num_agents(mesh) == 1
    with pytest.raises(ValueError, match="model_parallel"):
        make_global_mesh(model_parallel=3)
