"""Multi-controller PDSGD: N processes own N/world agents each.

The paper's threat model is honest-but-curious *separate parties*; this
launcher makes the party boundary an OS process boundary.  Each rank
process owns a contiguous block of agents — their Λ-keys (derived
in-process, never serialized), their data stream (`DataPipeline`
``agent_slice``), and their checkpoint shard (``<root>/host_<r>``) — and
the only bytes that ever cross a rank boundary are the framed mixed
messages ``v_ij = w_ij x_j − b_ij u_j`` of `dist.transport.SocketTransport`.

    PYTHONPATH=src python -m repro.launch.multihost \
        --world 4 --agents 4 --arch stablelm-3b-tiny --steps 20 \
        --checkpoint-dir /tmp/mh --checkpoint-every 5

Determinism contract
--------------------
Per-step keys, batches, coupling realizations, and B^k draws all derive
from the ABSOLUTE step index and the shared run seed, and every rank runs
the identical jitted per-agent program on identical inputs — so a
world=N run is bit-identical (final params AND captured wire stream) to
the world=1 run of this same driver at fault rate 0
(tests/test_multihost.py pins it).  ``--private-lambda-keys`` trades that
cross-world reproducibility for fully independent per-rank Λ roots drawn
from os.urandom (true key locality in deployment form).

Faults, quorum, and Λ-replay
----------------------------
A SIGKILLed rank is detected twice: the coordinator broadcasts
``{"dead": r}`` to the survivors' control sockets, and the transport
notices the dead peer (EOF/timeout) — from the next step the survivors
recompute the Metropolis coupling over the alive overlay
(`core.mixing.metropolis_from_mask`), which is doubly stochastic for
every realization.  On ``--resume`` all ranks restart from the QUORUM
step (the newest step every shard completed); ranks whose newest shard is
ahead roll back to it.  Because the previous run diverged from the
deterministic trajectory the moment a rank died (survivors ran with the
overlay), replaying those steps with the original Λ^k stream would pair
old draws with NEW gradients — exactly the key reuse the paper's privacy
argument forbids.  The launcher therefore bumps a **key generation**
counter in the spanning manifest whenever a run recorded casualties; the
generation is folded into every per-step key root, so a post-casualty
resume draws FRESH Λ^k (and B^k) from the quorum forward while a clean
resume stays a bit-identical replay (generation unchanged).

Shard layout
------------
    <root>/multihost.json        spanning manifest (rank 0 + launcher)
    <root>/wiretap_merged.npz    merged wire stream (launcher, --wiretap)
    <root>/host_0/step_<n>/...   rank 0's shard: ONLY its agents' rows
    <root>/host_0/manifest.json  per-shard manifest (CheckpointManager)
    <root>/host_0/wiretap.npz    rank 0's sender-side wire columns
    <root>/host_1/...

A shard holds {"x": (L, D) float32, "step"} — no key material, no other
rank's rows (asserted by tests/test_multihost.py).
"""
from __future__ import annotations

import argparse
import json
import os
import select
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np

from ..checkpoint import CheckpointManager
from ..checkpoint import io as ckpt_io
from ..dist.transport import (InProcessTransport, SocketTransport,
                              flatten_one, unflatten_one)
from .train import build_mixing, build_parser

__all__ = ["build_multihost_parser", "run_rank", "launch", "main",
           "host_dir", "quorum_step", "merge_wiretaps", "MANIFEST"]

MANIFEST = "multihost.json"


def host_dir(root: str, rank: int) -> str:
    return os.path.join(root, f"host_{rank}")


def quorum_step(root: str, world: int) -> int | None:
    """Newest step EVERY rank's shard has durably committed, or None."""
    common: set[int] | None = None
    for r in range(world):
        d = host_dir(root, r)
        steps = set(ckpt_io.complete_steps(d)) if os.path.isdir(d) else set()
        common = steps if common is None else (common & steps)
    return max(common) if common else None


def read_manifest(root: str) -> dict | None:
    path = os.path.join(root, MANIFEST)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def next_generation(root: str, resume: bool) -> int:
    """Λ-key generation for this run (see module docstring): bumped on a
    resume after a run that recorded casualties, carried otherwise."""
    if not resume:
        return 0
    man = read_manifest(root)
    if man is None:
        return 0
    gen = int(man.get("generation", 0))
    if man.get("casualties"):
        gen += 1
    return gen


def merge_wiretaps(root: str, world: int) -> str | None:
    """Gather per-rank sender-side wire columns into the dense stream.

    Each rank's ``host_<r>/wiretap.npz`` holds ``v`` (T, m, L, D) — the
    columns its own senders put on the wire — plus the step ids.  The
    merge concatenates along the sender axis over the steps ALL ranks
    captured, yielding the same (T, m, m, D) tensor a single-process
    ``--privacy-audit`` capture sees.  Returns the merged path (or None
    when a rank captured nothing).
    """
    blocks, step_sets = [], []
    for r in range(world):
        path = os.path.join(host_dir(root, r), "wiretap.npz")
        if not os.path.exists(path):
            return None
        with np.load(path) as z:
            blocks.append(z["v"])
            step_sets.append(list(z["steps"]))
    common = sorted(set(step_sets[0]).intersection(*map(set, step_sets)))
    if not common:
        return None
    sel = []
    for r in range(world):
        idx = [step_sets[r].index(s) for s in common]
        sel.append(blocks[r][idx])
    # Per-step blocks are (m, L, D) and `merge_captures` concats their
    # sender axis 1; these are stacked (T, m, L, D), so the sender axis
    # moved to 2.
    merged = np.concatenate(sel, axis=2)  # -> (T, m, m, D)
    out = os.path.join(root, "wiretap_merged.npz")
    np.savez(out, v=merged, steps=np.asarray(common, np.int64))
    return out


def build_multihost_parser() -> argparse.ArgumentParser:
    p = build_parser()
    p.description = "multi-controller PDSGD launcher / rank driver"
    p.add_argument("--world", type=int, default=1,
                   help="number of rank processes (agents % world == 0)")
    p.add_argument("--transport", default="auto",
                   choices=["auto", "socket", "inproc"],
                   help="auto: sockets when world > 1, in-process dense "
                        "reference otherwise")
    p.add_argument("--wiretap", action="store_true",
                   help="capture each rank's sender-side wire columns to "
                        "host_<r>/wiretap.npz; the launcher merges them "
                        "into wiretap_merged.npz (the cross-process "
                        "--privacy-audit stream)")
    p.add_argument("--private-lambda-keys", action="store_true",
                   help="derive each rank's Λ root from os.urandom instead "
                        "of the shared seed: true per-host key locality, "
                        "at the cost of cross-world bit-reproducibility")
    p.add_argument("--chaos-kill-rank", type=int, default=None,
                   help="rank that SIGKILLs itself mid-run (chaos test)")
    p.add_argument("--chaos-kill-step", type=int, default=None,
                   help="step at which --chaos-kill-rank dies")
    p.add_argument("--timeout", type=float, default=120.0,
                   help="socket/rendezvous timeout in seconds")
    p.add_argument("--frames-ahead", type=int, default=0,
                   help="0: blocking SocketTransport (lockstep exchange); "
                        ">0: PipelinedSocketTransport that stages frames "
                        "lazily, sends from a background thread, and lets "
                        "this rank run up to N steps ahead of its slowest "
                        "live peer")
    p.add_argument("--outbox-frames", type=int, default=64,
                   help="bounded send-queue depth for the pipelined "
                        "transport (backpressure when full)")
    # internal (launcher -> rank):
    p.add_argument("--rank", type=int, default=None, help=argparse.SUPPRESS)
    p.add_argument("--coord", default=None, help=argparse.SUPPRESS)
    p.add_argument("--generation", type=int, default=None,
                   help=argparse.SUPPRESS)
    return p


# -- control-plane plumbing (JSON lines over the rendezvous socket) -------


def _send_json(sock: socket.socket, obj: dict) -> None:
    sock.sendall((json.dumps(obj) + "\n").encode())


class _LineReader:
    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.buf = b""

    def poll(self, timeout: float = 0.0) -> list[dict]:
        """Drain whatever JSON lines are available within ``timeout``."""
        out = []
        while True:
            nl = self.buf.find(b"\n")
            if nl >= 0:
                line, self.buf = self.buf[:nl], self.buf[nl + 1:]
                if line.strip():
                    out.append(json.loads(line))
                continue
            try:
                if self.sock.fileno() < 0:  # closed under us
                    return out
                r, _, _ = select.select([self.sock], [], [],
                                        timeout if not out else 0.0)
            except (OSError, ValueError):
                return out
            if not r:
                return out
            try:
                part = self.sock.recv(65536)
            except OSError:
                return out
            if not part:
                return out
            self.buf += part

    def wait_for(self, key: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for msg in self.poll(min(1.0, deadline - time.monotonic())):
                if key in msg:
                    return msg
        raise TimeoutError(f"no {key!r} message from coordinator within "
                           f"{timeout}s")


# -- the per-rank driver --------------------------------------------------


def _fingerprint(args, rank: int) -> dict:
    """Identity of a multihost shard, recorded in its run_meta: a resume
    whose world/agents/rank/seed/arch disagree fails fast."""
    return {"format": 1, "world": int(args.world),
            "agents": int(args.agents), "rank": int(rank),
            "seed": int(args.seed), "arch": args.arch}


def run_rank(args) -> dict:
    """One controller process: own agents, own keys, own shard.

    Returns (and prints as the final JSON line) a summary with the final
    step, finiteness, a params digest, and timing.  Usable in-process for
    ``world == 1`` tests; the launcher always runs it as a subprocess.
    """
    import hashlib

    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..core.mixing import metropolis_from_mask
    from ..core.privacy import agent_key, clip_gradients, obfuscated_gradient, \
        sample_B
    from ..core.schedules import warmup_harmonic
    from ..data import make_lm_pipeline
    from ..models import build_model

    rank = args.rank or 0
    world, m = args.world, args.agents
    if m % world:
        raise ValueError(f"{m} agents do not split over {world} ranks")
    L = m // world
    lo, hi = rank * L, (rank + 1) * L
    root = args.checkpoint_dir
    if world > 1 and not root:
        raise ValueError("--world > 1 requires --checkpoint-dir (shards + "
                         "spanning manifest live there)")
    if args.resume and not root:
        raise ValueError("--resume requires --checkpoint-dir")
    if args.checkpoint_sync and args.checkpoint_writer:
        raise ValueError("--checkpoint-sync and --checkpoint-writer are "
                         "mutually exclusive")
    writer = "sync" if args.checkpoint_sync else args.checkpoint_writer

    # --- rendezvous -----------------------------------------------------
    coord = reader = None
    listen = None
    endpoints: dict[int, tuple[str, int]] = {}
    use_socket = args.transport == "socket" or (
        args.transport == "auto" and world > 1)
    if world > 1:
        if args.coord is None:
            raise ValueError("rank mode with --world > 1 needs --coord "
                             "(spawn through the launcher)")
        listen = socket.socket()
        listen.bind(("127.0.0.1", 0))
        listen.listen(world)
        host, port = args.coord.rsplit(":", 1)
        coord = socket.create_connection((host, int(port)),
                                         timeout=args.timeout)
        _send_json(coord, {"hello": rank,
                           "port": listen.getsockname()[1]})
        reader = _LineReader(coord)
        msg = reader.wait_for("endpoints", args.timeout)
        endpoints = {int(r): tuple(ep) for r, ep in msg["endpoints"].items()}

    # --- model / mixing / data ------------------------------------------
    cfg = get_config(args.arch)
    bundle = build_model(cfg)
    mixing = build_mixing(args)
    sched = warmup_harmonic(args.lr, hold=args.warmup_hold)
    pipeline = make_lm_pipeline(cfg.vocab_size, m, args.per_agent_batch,
                                args.seq_len, seed=args.seed)
    template = bundle.init(jax.random.key(args.seed))
    flat0 = flatten_one(template)
    D = flat0.shape[0]
    x = np.tile(flat0, (L, 1))  # (L, D) — this rank's agents

    adj_off = np.asarray(mixing.base_mask, np.float32)
    adjacency = (adj_off > 0).astype(np.int64)
    eye = jnp.eye(m, dtype=jnp.float32)

    # --- keys ------------------------------------------------------------
    gen = args.generation
    if gen is None:
        gen = next_generation(root, args.resume) if root else 0
    shared_root = jax.random.key(args.seed + 1)
    if gen > 0:
        # Fresh draws after a casualty (see module docstring); double
        # fold_in so a generation can never collide with a step index.
        shared_root = jax.random.fold_in(
            jax.random.fold_in(shared_root, 0x5eed), gen)
    if args.private_lambda_keys:
        lam_root = jax.random.key(
            int.from_bytes(os.urandom(4), "little"))
    else:
        lam_root = shared_root

    # --- the jitted per-agent program ------------------------------------
    # One compiled function, identical on every rank; loss/grad/Λ/obfuscate
    # per agent.  The schedule and agent_key both consume the traced
    # absolute step, so resume replays are exact.
    kappa = args.grad_clip_kappa

    @jax.jit
    def fwd(p_j, batch_j, stepv, aidx, sk):
        loss, g = jax.value_and_grad(bundle.loss_fn)(p_j, batch_j)
        if kappa is not None:
            g = clip_gradients(g, kappa)
        lam_bar = jnp.asarray(sched(stepv.astype(jnp.float32), 0),
                              jnp.float32)
        u = obfuscated_gradient(
            agent_key(jax.random.fold_in(sk, 1), stepv, aidx), g, lam_bar)
        return loss, u

    def couple(k: int, alive: np.ndarray | None):
        """(W, B, support) for step k — realized over the believed-alive
        set.  Eager jnp (no multi-op jit): the v math downstream must
        stay FMA-free for cross-transport bit-parity."""
        kj = jnp.asarray(k, jnp.int32)
        W, support, mask = mixing.realize(kj)
        if alive is not None:
            base = mask if mask is not None else jnp.asarray(adj_off)
            a = jnp.asarray(alive, jnp.float32)
            mask = base * a[:, None] * a[None, :]
            W = metropolis_from_mask(mask)
            support = mask + eye
        sk = jax.random.fold_in(shared_root, k)
        B = sample_B(agent_key(jax.random.fold_in(sk, 2), kj, 0), support)
        return (np.asarray(W, np.float32), np.asarray(B, np.float32),
                np.asarray(support, np.float32))

    # --- transport -------------------------------------------------------
    if use_socket and world > 1:
        # Per-run frame auth: every rank derives the same key from
        # (seed, generation), so a frame from another run — or from a
        # stale pre-rollback generation — fails its tag at the pump.
        from ..dist.transport import PipelinedSocketTransport, \
            derive_wire_secret
        secret = derive_wire_secret(args.seed, gen)
        if args.frames_ahead > 0:
            transport = PipelinedSocketTransport(
                adjacency, rank, world, endpoints, listen,
                timeout=args.timeout, secret=secret,
                outbox_frames=args.outbox_frames,
                frames_ahead=args.frames_ahead)
        else:
            transport = SocketTransport(adjacency, rank, world, endpoints,
                                        listen, timeout=args.timeout,
                                        secret=secret)
    else:
        transport = InProcessTransport(adjacency)

    # --- checkpoint shard ------------------------------------------------
    manager = None
    start = 0
    like = {"x": jnp.zeros((L, D), jnp.float32), "step": jnp.int32(0)}
    run_meta = {"mixing": mixing.fingerprint(),
                "multihost": _fingerprint(args, rank)}
    if root:
        my_dir = host_dir(root, rank)
        if args.resume:
            q = quorum_step(root, world)
            if q is None:
                raise FileNotFoundError(
                    f"--resume: no step completed by ALL {world} shards "
                    f"under {root!r}; drop --resume for a fresh run")
            stored = ckpt_io.read_run_meta(my_dir, q)
            if stored.get("mixing") != run_meta["mixing"]:
                raise ValueError(
                    f"--resume: shard step_{q:08d} was written with mixing "
                    f"config {stored.get('mixing')}, this run built "
                    f"{run_meta['mixing']}; pass matching --topology* flags")
            if stored.get("multihost") != run_meta["multihost"]:
                raise ValueError(
                    f"--resume: shard step_{q:08d} belongs to deployment "
                    f"{stored.get('multihost')}, this run is "
                    f"{run_meta['multihost']}")
            newest = ckpt_io.latest_step(my_dir)
            manager = CheckpointManager(my_dir, keep_last=args.keep_last,
                                        keep_every=args.keep_every,
                                        writer=writer,
                                        fresh=False, run_meta=run_meta)
            loaded = ckpt_io.load_checkpoint(my_dir, q, like=like)
            if int(loaded["step"]) != q:
                raise ValueError(
                    f"shard step_{q:08d} holds state.step="
                    f"{int(loaded['step'])}; refusing a mislabeled shard")
            x = np.asarray(loaded["x"], np.float32).copy()
            start = q
            print(json.dumps({"rank": rank, "resumed_from": q,
                              "own_newest": newest,
                              "rolled_back": bool(newest is not None
                                                  and newest > q),
                              "generation": gen}), flush=True)
        else:
            manager = CheckpointManager(my_dir, keep_last=args.keep_last,
                                        keep_every=args.keep_every,
                                        writer=writer,
                                        fresh=True, run_meta=run_meta)
        if rank == 0:
            # Rank-0 spanning manifest; the launcher fills in casualties
            # after the run.
            ckpt_io._atomic_write_json(os.path.join(root, MANIFEST), {
                "format": 1, "world": world, "agents": m, "per_rank": L,
                "arch": args.arch, "seed": int(args.seed),
                "steps": int(args.steps), "generation": gen,
                "transport": ("socket" if (use_socket and world > 1)
                              else "inproc"),
                "hosts": [f"host_{r}" for r in range(world)],
                "casualties": [],
            })

    # --- the loop --------------------------------------------------------
    dead_agents: set[int] = set()
    dead_ranks: set[int] = set()
    fault_log: list[dict] = []
    taps: list[np.ndarray] = []
    tap_steps: list[int] = []
    nonfinite = 0
    losses = np.zeros(L, np.float32)
    compute_s = 0.0  # local fwd/grad/obfuscate wall time
    comm_s = 0.0     # wall time inside transport.exchange
    t0 = time.monotonic()
    k = start
    try:
        while k < args.steps:
            if (args.chaos_kill_rank == rank
                    and args.chaos_kill_step == k):
                os.kill(os.getpid(), signal.SIGKILL)
            # Control-plane death notices (non-blocking).
            if reader is not None:
                for msg in reader.poll(0.0):
                    if "dead" in msg:
                        dead_ranks.add(int(msg["dead"]))
            for r in set(getattr(transport, "dead_ranks", ())):
                dead_ranks.add(r)
            if dead_ranks:
                newly = {a for r in dead_ranks
                         for a in range(r * L, (r + 1) * L)} - dead_agents
                if isinstance(transport, SocketTransport):
                    for r in dead_ranks:
                        transport.mark_dead(r)
                if newly:
                    dead_agents |= newly
            alive = None
            if dead_agents:
                alive = np.ones(m, np.float32)
                alive[sorted(dead_agents)] = 0.0
            W, B, support = couple(k, alive)
            if dead_agents and (not fault_log
                                or fault_log[-1]["dead"]
                                != sorted(dead_agents)):
                live = np.asarray(sorted(set(range(m)) - dead_agents))
                Wl = W[np.ix_(live, live)]
                fault_log.append({
                    "step": k, "dead": sorted(dead_agents),
                    "row_sum_err": float(np.abs(Wl.sum(1) - 1).max()),
                    "col_sum_err": float(np.abs(Wl.sum(0) - 1).max()),
                })
            batch = pipeline.batch_at(k, agent_slice=(lo, hi))
            u = np.empty_like(x)
            sk_lam = jax.random.fold_in(lam_root, k)
            kj = jnp.asarray(k, jnp.int32)
            tc = time.monotonic()
            for l in range(L):
                p_j = unflatten_one(x[l], template)
                b_j = {name: leaf[l] for name, leaf in batch.items()}
                loss, u_tree = fwd(p_j, b_j, kj, jnp.asarray(lo + l,
                                                             jnp.int32),
                                   sk_lam)
                losses[l] = float(loss)
                u[l] = flatten_one(u_tree)
            tx = time.monotonic()
            compute_s += tx - tc
            out = transport.exchange(x, u, W, B, step=k,
                                     capture=args.wiretap)
            comm_s += time.monotonic() - tx
            if args.wiretap:
                out, cols = out
                taps.append(cols)
                tap_steps.append(k)
            finite = bool(np.isfinite(out).all())
            if not finite:
                nonfinite += 1
                if args.nan_policy == "skip":
                    out = x  # hold the last finite local block
            x = np.asarray(out, np.float32)
            k += 1
            if manager is not None and (
                    k % args.checkpoint_every == 0):
                manager.save(k, {"x": jnp.asarray(x),
                                 "step": jnp.int32(k)})
            if (k - 1) % args.log_every == 0 or k == args.steps:
                print(json.dumps({
                    "rank": rank, "step": k - 1,
                    "loss_local": round(float(losses.mean()), 6),
                    "dead": sorted(dead_agents),
                    "elapsed_s": round(time.monotonic() - t0, 2)}),
                    flush=True)
        if manager is not None:
            manager.save(max(start, args.steps),
                         {"x": jnp.asarray(x),
                          "step": jnp.int32(max(start, args.steps))})
    finally:
        if manager is not None:
            manager.close()
        transport.close()

    steps_run = max(0, args.steps - start)
    us_per_step = ((time.monotonic() - t0) / steps_run * 1e6
                   if steps_run else 0.0)
    # Transport-level counters (zeros for InProcessTransport): how long
    # this rank sat in/waiting on the wire vs. computing locally.
    comm = {
        "transport": type(transport).__name__,
        "steps": steps_run,
        "compute_s": round(compute_s, 4),
        "comm_s": round(comm_s, 4),
        "comm_wait_s": round(float(getattr(transport, "comm_wait_s",
                                           0.0)), 4),
        "drops": int(getattr(transport, "drops", 0)),
        "tag_failures": int(getattr(transport, "tag_failures", 0)),
    }
    if root:
        if args.wiretap and taps:
            np.savez(os.path.join(host_dir(root, rank), "wiretap.npz"),
                     v=np.stack(taps),
                     steps=np.asarray(tap_steps, np.int64))
        if fault_log or isinstance(transport, SocketTransport):
            ckpt_io._atomic_write_json(
                os.path.join(host_dir(root, rank), "fault_log.json"),
                {"events": fault_log, "comm": comm})
    summary = {
        "rank": rank, "final_step": int(max(start, args.steps)),
        "finite": bool(np.isfinite(x).all()),
        "x_sha256": hashlib.sha256(
            np.ascontiguousarray(x).tobytes()).hexdigest(),
        "nonfinite_steps": nonfinite,
        "dead_seen": sorted(dead_ranks),
        "generation": gen,
        "us_per_step": round(us_per_step, 1),
        "comm": comm,
    }
    print(json.dumps({"rank_summary": summary}), flush=True)
    if coord is not None:
        try:
            _send_json(coord, {"done": rank, **summary})
            coord.close()
        except OSError:
            pass
    return summary


# -- the launcher ---------------------------------------------------------


class _Coordinator(threading.Thread):
    """Rendezvous + death broadcast.  Collects one hello per rank, then
    broadcasts the endpoint table; afterwards relays launcher-detected
    deaths to the surviving control connections."""

    def __init__(self, world: int, timeout: float):
        super().__init__(name="repro-multihost-coord", daemon=True)
        self.world = world
        self.timeout = timeout
        self.listen = socket.socket()
        self.listen.bind(("127.0.0.1", 0))
        self.listen.listen(world)
        self.port = self.listen.getsockname()[1]
        self.conns: dict[int, socket.socket] = {}
        self.done: dict[int, dict] = {}
        self.lock = threading.Lock()
        self.ready = threading.Event()
        self.stop = threading.Event()

    def run(self):
        endpoints = {}
        deadline = time.monotonic() + self.timeout
        self.listen.settimeout(1.0)
        while len(self.conns) < self.world:
            if self.stop.is_set() or time.monotonic() > deadline:
                return
            try:
                conn, _ = self.listen.accept()
            except socket.timeout:
                continue
            reader = _LineReader(conn)
            msg = reader.wait_for("hello", self.timeout)
            r = int(msg["hello"])
            with self.lock:
                self.conns[r] = conn
            endpoints[r] = ["127.0.0.1", int(msg["port"])]
        table = {"endpoints": endpoints}
        with self.lock:
            for conn in self.conns.values():
                try:
                    _send_json(conn, table)
                except OSError:
                    pass
        self.ready.set()
        # Drain done-messages until stopped.
        readers = {r: _LineReader(c) for r, c in self.conns.items()}
        while not self.stop.is_set():
            with self.lock:
                items = [(r, rd) for r, rd in readers.items()
                         if r in self.conns]  # broadcast_dead closes conns
            for r, reader in items:
                for msg in reader.poll(0.05):
                    if "done" in msg:
                        self.done[r] = msg
            time.sleep(0.02)

    def broadcast_dead(self, rank: int):
        with self.lock:
            conn = self.conns.pop(rank, None)
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass
            for r, conn in self.conns.items():
                try:
                    _send_json(conn, {"dead": rank})
                except OSError:
                    pass

    def shutdown(self):
        self.stop.set()
        self.join(timeout=5.0)
        with self.lock:
            for conn in self.conns.values():
                try:
                    conn.close()
                except OSError:
                    pass
        try:
            self.listen.close()
        except OSError:
            pass


def rank_platform(env: dict) -> str:
    """The JAX platform a rank process started with ``env`` would use.
    Read from ``JAX_PLATFORMS`` when that excludes the TPU; otherwise
    probed in a short-lived child, so this launcher never loads the TPU
    runtime itself (a process that has loaded it holds the chips)."""
    platforms = [p for p in env.get("JAX_PLATFORMS", "").split(",") if p]
    if platforms and "tpu" not in platforms:
        return platforms[0]
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        env=env, capture_output=True, text=True, timeout=300)
    if probe.returncode != 0:
        raise RuntimeError("probing the rank platform failed:\n"
                           + probe.stderr[-2000:])
    return probe.stdout.strip().splitlines()[-1]


def check_rank_platform(world: int, env: dict) -> None:
    """Refuse to start more TPU-using rank processes than this host can
    give a chip each.  A rank process opens every chip it can see, and a
    chip belongs to one process at a time, so a host runs at most one TPU
    rank: the others would fail or hang at start-up."""
    if world > 1 and rank_platform(env) == "tpu":
        raise ValueError(
            f"--world {world} would start {world} TPU-using rank processes "
            "on this host, but the first one holds all of its chips; run "
            "one rank per host, or put the ranks on the CPU with "
            "JAX_PLATFORMS=cpu")


def launch(args) -> dict:
    """Spawn ``--world`` rank processes, monitor them, merge artifacts.

    Returns the run summary (also printed as the final JSON line):
    per-rank summaries, casualties (ranks that died by signal), and the
    spanning-manifest path.  Exit status is nonzero iff a NON-killed rank
    failed.
    """
    world = args.world
    root = args.checkpoint_dir
    if args.agents % world:
        raise ValueError(f"--agents {args.agents} does not split over "
                         f"--world {world}")
    gen = next_generation(root, args.resume) if root else 0
    if world == 1 and args.chaos_kill_rank is None:
        summary = run_rank(argparse.Namespace(**{**vars(args), "rank": 0,
                                                 "generation": gen}))
        merged = merge_wiretaps(root, 1) if (args.wiretap and root) else None
        out = {"world": 1, "ranks": {"0": summary}, "casualties": [],
               "generation": gen, "wiretap_merged": merged, "ok": True}
        _finalize(root, out)
        print(json.dumps({"multihost_summary": out}), flush=True)
        return out

    env = dict(os.environ)
    src_dir = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src_dir + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    check_rank_platform(world, env)
    coord = _Coordinator(world, args.timeout)
    coord.start()
    procs: dict[int, subprocess.Popen] = {}
    passthrough = _args_to_argv(args)
    for r in range(world):
        cmd = [sys.executable, "-m", "repro.launch.multihost",
               *passthrough, "--rank", str(r),
               "--coord", f"127.0.0.1:{coord.port}",
               "--generation", str(gen)]
        procs[r] = subprocess.Popen(cmd, env=env)
    casualties: list[int] = []
    alive = set(procs)
    try:
        while alive:
            time.sleep(0.1)
            for r in sorted(alive):
                rc = procs[r].poll()
                if rc is None:
                    continue
                alive.discard(r)
                if rc != 0:
                    casualties.append(r)
                    coord.broadcast_dead(r)
    finally:
        coord.shutdown()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    merged = None
    if args.wiretap and root:
        merged = merge_wiretaps(root, world)
    ok = all(procs[r].returncode == 0 for r in range(world)
             if r not in casualties)
    out = {"world": world, "agents": args.agents,
           "ranks": {str(r): coord.done.get(r) for r in range(world)},
           "casualties": sorted(casualties), "generation": gen,
           "wiretap_merged": merged, "ok": ok}
    _finalize(root, out)
    print(json.dumps({"multihost_summary": out}), flush=True)
    return out


def _finalize(root: str | None, out: dict) -> None:
    """Record the run outcome in the spanning manifest (casualties drive
    the NEXT run's key generation)."""
    if not root:
        return
    man = read_manifest(root) or {"format": 1}
    man["casualties"] = out["casualties"]
    man["generation"] = out["generation"]
    man["ok"] = out["ok"]
    ckpt_io._atomic_write_json(os.path.join(root, MANIFEST), man)


def _args_to_argv(args) -> list[str]:
    """Re-serialize parsed args for rank subprocesses (programmatic
    `launch` callers — tests — don't come through sys.argv)."""
    argv: list[str] = []
    skip = {"rank", "coord", "generation"}
    flags = {"wiretap", "private_lambda_keys", "resume", "privacy_audit",
             "checkpoint_sync"}
    for name, val in vars(args).items():
        if name in skip or val is None:
            continue
        opt = "--" + name.replace("_", "-")
        if name in flags or isinstance(val, bool):
            if val:
                argv.append(opt)
            continue
        argv.extend([opt, str(val)])
    return argv


def main(argv=None):
    args = build_multihost_parser().parse_args(argv)
    if args.rank is not None:
        run_rank(args)
        return 0
    out = launch(args)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
