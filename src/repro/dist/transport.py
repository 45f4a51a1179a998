"""The transport seam: Eq. (3)'s neighbor exchange written once.

Every execution mode of the paper's update

    x_i' = w_ii x_i - b_ii u_i  +  sum_{j in N_i} (w_ij x_j - b_ij u_j)

moves the SAME quantity between agents: the sender-mixed message
``v_ij = w_ij x_j - b_ij u_j`` (`link_message`).  Neither x_j nor u_j —
and never any Lambda-key material — crosses an agent boundary; that is
the paper's Sec. III privacy architecture, and this module makes it a
literal interface so the math exists in one place no matter where the
boundary physically is:

* `InProcessTransport`  — all agents in one process (host numpy); the
                          readable reference implementation and the
                          world=1 anchor of `launch.multihost`.
* `ShardMapTransport`   — one agent per mesh shard, `lax.ppermute` per
                          torus direction (the device-collective flavor
                          of `collectives.torus_gossip_pdsgd`).
* `SocketTransport`     — one process per agent block, TCP framing: the
                          only bytes on the wire are (step, sender,
                          receiver, len, v_ij payload).  This is the
                          multi-controller deployment channel.
* `PipelinedSocketTransport` — the overlapped flavor of the same wire
                          protocol: a bounded-outbox send thread and an
                          eager receive thread pump frames while the
                          caller computes, per-link lazy staging replaces
                          the dense column materialization, and a
                          ``frames_ahead`` window lets a rank start step
                          k+1's sends before step k's stragglers land.
                          Bit-identical trajectories to `SocketTransport`
                          (same frames, same accumulation order).

Canonical accumulation order
----------------------------
Floating-point addition does not associate, so "the same math" needs ONE
contract: each receiver accumulates its self term first, then every
neighbor contribution in ascending global sender id.  All three
transports honor it, which is what lets `tests/test_transport.py` pin
their outputs bit-for-bit against each other (numpy vs device arrays:
XLA contracts ``w*x - b*u`` into an FMA *inside a jitted fusion*, so the
traced transport computes every v and self term EAGERLY — one XLA op per
primitive, bit-identical to numpy — and jits only the permute+add body,
where plain add chains are exact).

(`collectives.torus_gossip_pdsgd` predates this seam and keeps its
direction-order accumulation — its trajectories are bit-anchored by
existing tests — but its per-link message math now routes through
`link_message`, so the privacy-critical formula is shared.)

Capture convention
------------------
``exchange(..., capture=True)`` also returns the dense wire tensor in
`privacy.observe.wire_messages` layout: V[i, j] = v_ij with the diagonal
zeroed (v_jj never crosses any boundary).  A transport that only owns a
block of senders returns its (m, L, D) column block; `merge_captures`
reassembles the global tensor — the gather step that makes a
multi-process ``--privacy-audit`` see the same stream as a single
process.  Entries off the realized support are exact (signed) zeros.
"""
from __future__ import annotations

import hashlib
import hmac
import os
import queue
import select
import socket
import struct
import threading
import time
from typing import Any, Sequence

import numpy as np

__all__ = [
    "link_message",
    "flatten_one",
    "unflatten_one",
    "neighbor_lists",
    "accumulate",
    "capture_columns",
    "merge_captures",
    "Transport",
    "InProcessTransport",
    "ShardMapTransport",
    "SocketTransport",
    "PipelinedSocketTransport",
    "FRAME_HEADER",
    "WIRE_TAG_SIZE",
    "derive_wire_secret",
]

Pytree = Any


def link_message(w, b, x, u):
    """THE per-link message: v = w * x - b * u.

    Works on numpy and (eager) jax operands alike; each primitive rounds
    separately.  Do not call it inside a jitted region when bit-parity
    with the host transports matters — XLA fuses the pattern into an FMA
    there (see the module docstring).
    """
    return (w * x) - (b * u)


def flatten_one(tree: Pytree) -> np.ndarray:
    """One agent's pytree -> flat (D,) f32 vector.

    Per-leaf ravel in `jax.tree.leaves` order, concatenated — exactly row
    j of `privacy.observe.flatten_agents` applied to the stacked tree, so
    host-side transports and the traced capture paths index the same D.
    """
    import jax
    leaves = jax.tree.leaves(tree)
    flat = [np.asarray(l, dtype=np.float32).reshape(-1) for l in leaves]
    return np.concatenate(flat) if len(flat) > 1 else flat[0]


def unflatten_one(vec: np.ndarray, like: Pytree) -> Pytree:
    """Inverse of `flatten_one` against a template pytree (exact: every
    element is copied through reshape, never recombined)."""
    import jax
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape, dtype=np.int64)) if l.ndim else 1
        out.append(np.asarray(vec[off:off + n], dtype=np.float32)
                   .reshape(l.shape))
        off += n
    if off != len(vec):
        raise ValueError(f"flat vector has {len(vec)} elements; template "
                         f"needs {off}")
    return jax.tree.unflatten(treedef, out)


def neighbor_lists(adjacency: np.ndarray) -> list[np.ndarray]:
    """Ascending neighbor ids per agent from a symmetric 0/1 adjacency
    (diagonal ignored) — the canonical accumulation order."""
    A = np.asarray(adjacency)
    if A.ndim != 2 or A.shape[0] != A.shape[1]:
        raise ValueError(f"adjacency must be square, got {A.shape}")
    if not np.array_equal(A, A.T):
        raise ValueError("adjacency must be symmetric (undirected links)")
    off = A * (1 - np.eye(A.shape[0], dtype=A.dtype))
    return [np.flatnonzero(off[i]) for i in range(A.shape[0])]


def accumulate(i: int, self_term: np.ndarray,
               contribs: dict[int, np.ndarray]) -> np.ndarray:
    """Canonical receiver-side reduction: self term + contributions in
    ascending sender id.  Shared by the in-process and socket transports
    (the shard_map body reproduces the same order in-trace)."""
    acc = self_term
    for j in sorted(contribs):
        if j == i:
            raise ValueError(f"agent {i} cannot receive its own v_ii")
        acc = acc + contribs[j]
    return acc


def capture_columns(W: np.ndarray, B: np.ndarray, x: np.ndarray,
                    u: np.ndarray, lo: int = 0) -> np.ndarray:
    """Sender-side wire columns: out[i, l] = v_{i, lo+l} with the v_jj
    diagonal zeroed — the (m, L, D) block of `observe.wire_messages` a
    rank owning senders [lo, lo+L) can emit by itself."""
    L = x.shape[0]
    cols = (W[:, lo:lo + L, None] * x[None, :, :]
            - B[:, lo:lo + L, None] * u[None, :, :])
    for l in range(L):
        cols[lo + l, l, :] = 0.0
    return cols


def merge_captures(blocks: Sequence[np.ndarray]) -> np.ndarray:
    """Reassemble per-rank (m, L, D) column blocks (rank order) into the
    dense (m, m, D) wire tensor — the gather step of a cross-process
    privacy audit."""
    return np.concatenate(list(blocks), axis=1)


class Transport:
    """One neighbor exchange per call over the local agent block.

    ``exchange(x_local, u_local, W, B, step=..., capture=...)`` applies
    Eq. (3) for the agents this transport owns and returns their updated
    (L, D) block — with ``capture=True``, also the (m, L, D) wire column
    block of the local senders.  W/B are the step's realized dense
    coupling matrices; entries off this transport's base adjacency must
    be zero.
    """

    num_agents: int
    local_lo: int
    local_hi: int

    @property
    def local_agents(self) -> range:
        return range(self.local_lo, self.local_hi)

    def exchange(self, x_local, u_local, W, B, *, step: int = 0,
                 capture: bool = False):
        raise NotImplementedError

    def close(self) -> None:  # pragma: no cover - trivial default
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class InProcessTransport(Transport):
    """All m agents local; pure host numpy.  The reference transport:
    `launch.multihost` world=1 runs on it, and the property tests pin the
    other two transports against its bits."""

    def __init__(self, adjacency: np.ndarray):
        self._nbrs = neighbor_lists(adjacency)
        self.num_agents = len(self._nbrs)
        self.local_lo, self.local_hi = 0, self.num_agents

    def exchange(self, x_local, u_local, W, B, *, step: int = 0,
                 capture: bool = False):
        x = np.asarray(x_local, dtype=np.float32)
        u = np.asarray(u_local, dtype=np.float32)
        W = np.asarray(W, dtype=np.float32)
        B = np.asarray(B, dtype=np.float32)
        m = self.num_agents
        if x.shape[0] != m:
            raise ValueError(f"expected all {m} agents local, got "
                             f"{x.shape[0]}")
        out = np.empty_like(x)
        for i in range(m):
            contribs = {int(j): link_message(W[i, j], B[i, j], x[j], u[j])
                        for j in self._nbrs[i]}
            out[i] = accumulate(i, link_message(W[i, i], B[i, i], x[i],
                                                u[i]), contribs)
        if not capture:
            return out
        return out, capture_columns(W, B, x, u, lo=0)


class ShardMapTransport(Transport):
    """One agent per ("pod", "data") mesh coordinate, `lax.ppermute` per
    torus direction.

    The per-link v and self terms are computed EAGERLY (bit-parity with
    the host transports — see module docstring); the jitted shard_map
    body only permutes and accumulates, re-ordering the received
    directions by global sender id so the canonical order holds even
    where direction order disagrees with it (e.g. receiver 0 on a ring
    hears direction +1 from sender m-1 but direction -1 from sender 1).
    """

    def __init__(self, mesh, n_data: int | None = None,
                 n_pod: int | None = None):
        shape = dict(getattr(mesh, "shape", {}))
        self.mesh = mesh
        self.n_pod = n_pod if n_pod is not None else shape.get("pod", 1)
        self.n_data = n_data if n_data is not None else shape.get("data", 1)
        self.num_agents = self.n_pod * self.n_data
        self.local_lo, self.local_hi = 0, self.num_agents
        from .collectives import _directions
        self._dirs = _directions(self.n_data, self.n_pod)
        self._body = None  # compiled lazily (needs D)

    def _make_body(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        dirs, n_data, n_pod = self._dirs, self.n_data, self.n_pod
        axes = tuple(a for a in ("pod", "data")
                     if self.mesh.shape.get(a, 1) > 1) or ("data",)
        spec = axes[0] if len(axes) == 1 else axes

        def body(self_loc, v_loc):
            # self_loc (1, D); v_loc (1, ndirs, D) — sender-side messages.
            pod = (jax.lax.axis_index("pod") if "pod" in axes
                   else jnp.int32(0))
            data = (jax.lax.axis_index("data") if "data" in axes
                    else jnp.int32(0))
            contribs, sids = [], []
            for di, (axis, size, shift) in enumerate(dirs):
                perm = [(d, (d + shift) % size) for d in range(size)]
                shifted = jax.lax.ppermute(v_loc[:, di], axis, perm)
                if axis == "data":
                    sid = pod * n_data + (data - shift) % n_data
                else:
                    sid = ((pod - shift) % n_pod) * n_data + data
                contribs.append(shifted)
                sids.append(sid)
            order = jnp.argsort(jnp.stack(sids))
            stack = jnp.stack(contribs)  # (ndirs, 1, D)
            acc = self_loc
            for r in range(len(dirs)):
                acc = acc + stack[order[r]]
            return acc

        return jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(spec), P(spec)),
            out_specs=P(spec), check_vma=False))

    def exchange(self, x_local, u_local, W, B, *, step: int = 0,
                 capture: bool = False):
        import jax.numpy as jnp
        from . import collectives as C

        x = jnp.asarray(np.asarray(x_local, np.float32))
        u = jnp.asarray(np.asarray(u_local, np.float32))
        Wj = jnp.asarray(np.asarray(W, np.float32))
        Bj = jnp.asarray(np.asarray(B, np.float32))
        # Exact per-entry extraction (einsum against 0/1 permutation
        # matrices copies, never recombines).
        tabs = C.directional_weights(Wj, self.n_data, self.n_pod)
        b_rows = C.rows_from_dense(Bj, self.n_data, self.n_pod)
        # Eager v/self math: one XLA op per primitive => numpy bits.
        self_term = link_message(tabs["w_self"][:, None],
                                 b_rows[:, 0, None], x, u)
        v_dirs = [link_message(tabs["w_dir"][:, di, None],
                               b_rows[:, 1 + di, None], x, u)
                  for di in range(len(self._dirs))]
        v_stack = jnp.stack(v_dirs, axis=1)  # (m, ndirs, D)
        if self._body is None:
            self._body = self._make_body()
        out = np.asarray(self._body(self_term, v_stack))
        if not capture:
            return out
        # Scatter sender-side taps to the dense layout: V[i, j] = v_dirs
        # [d][j] where i = shift_d(j).
        mats = C._perm_matrices(self.n_data, self.n_pod)
        V = np.zeros((self.num_agents, self.num_agents) + (x.shape[1],),
                     np.float32)
        for di, Pm in enumerate(mats):
            vd = np.asarray(v_dirs[di])
            ii, jj = np.nonzero(Pm)
            V[ii, jj] = vd[jj]
        return out, V


# -- the inter-process channel ------------------------------------------

# Wire frame: little-endian (step int64, sender int32, receiver int32,
# payload nbytes uint32) + raw f32 v_ij payload.  NOTHING else is ever
# serialized — asserted byte-for-byte by tests/test_transport.py.  With a
# per-run ``secret``, an HMAC-SHA256 tag over (header || payload) follows
# each frame: still only v bytes plus an authenticator that depends on
# them — no key material and no plaintext beyond v crosses the wire.
FRAME_HEADER = struct.Struct("<qiiI")
_HELLO = struct.Struct("<i")
WIRE_TAG_SIZE = hashlib.sha256().digest_size  # 32


def derive_wire_secret(seed: int, generation: int = 0) -> bytes:
    """The per-run frame-auth key every rank derives independently.

    Hashed from the shared run seed and the Λ-key generation (see
    `launch.multihost`), so all ranks of one run agree and a stale rank
    from a pre-rollback generation is rejected at the transport, not just
    at the key schedule.  ``REPRO_WIRE_SECRET`` overrides for deployments
    that inject a real secret (the seed-derived default authenticates
    framing errors and cross-run mixups, not a malicious peer who knows
    the seed).
    """
    env = os.environ.get("REPRO_WIRE_SECRET")
    if env:
        return env.encode()
    return hashlib.sha256(
        f"repro-wire|{int(seed)}|{int(generation)}".encode()).digest()


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    """Read exactly n bytes, or None on EOF/reset (peer death)."""
    buf = b""
    while len(buf) < n:
        try:
            part = sock.recv(n - len(buf))
        except (ConnectionError, OSError):
            return None
        if not part:
            return None
        buf += part
    return buf


class SocketTransport(Transport):
    """TCP neighbor exchange for a process owning agents [lo, lo+L).

    Only the framed ``v_ij`` payloads cross the process boundary; links
    between two local agents never touch a socket.  A peer that dies
    (connection reset/EOF, or ``timeout`` with frames still owed) is
    marked in ``dead_ranks`` and its contributions are dropped for the
    current step — the caller re-realizes the coupling over survivors
    from the next step (see `launch.multihost`).

    ``audit_wire=True`` records every sent frame verbatim in
    ``sent_frames`` so a test can prove the wire carries v bytes and
    nothing else.

    Counters: ``drops`` is owned by `exchange` — it counts, at
    accumulate time, every remote contribution a local agent needed this
    step but did not get (so a dead peer's links add to it EVERY step
    they stay down, whether the peer died mid-pump or steps ago);
    ``tag_failures`` counts frames rejected by HMAC verification;
    ``comm_wait_s`` accumulates wall time spent waiting on the wire
    (the receive pump here; both the frames_ahead gate and the
    needed-frames wait in the pipelined subclass).

    ``secret`` (a per-run shared key, typically `derive_wire_secret`)
    turns on frame authentication: each frame carries an HMAC-SHA256 tag
    over header+payload, and the pump rejects any frame whose tag is
    missing, truncated, or wrong — the sending channel is marked dead
    (``tag_failures`` counts rejections) and its contributions drop for
    the step, exactly the peer-death path.  ``None`` keeps the original
    unauthenticated framing byte-for-byte.
    """

    def __init__(self, adjacency: np.ndarray, rank: int, world: int,
                 endpoints: dict[int, tuple[str, int]],
                 listen_sock: socket.socket, *, timeout: float = 60.0,
                 audit_wire: bool = False, secret: bytes | None = None):
        self._nbrs = neighbor_lists(adjacency)
        m = len(self._nbrs)
        if m % world:
            raise ValueError(f"{m} agents do not split over {world} ranks")
        self.num_agents = m
        self.rank, self.world = rank, world
        self.block = m // world
        self.local_lo = rank * self.block
        self.local_hi = self.local_lo + self.block
        self.timeout = timeout
        self.audit_wire = audit_wire
        self.secret = secret
        self.tag_failures = 0  # frames rejected by HMAC verification
        self.sent_frames: list[bytes] = []
        self.dead_ranks: set[int] = set()
        self.drops = 0  # needed contributions missing at accumulate time
        self.comm_wait_s = 0.0  # wall time spent waiting on the wire
        self._listen = listen_sock
        self._socks: dict[int, socket.socket] = {}
        self._rbuf: dict[tuple[int, int, int], np.ndarray] = {}
        # Peer ranks that own at least one neighbor of a local agent.
        peers: set[int] = set()
        for j in self.local_agents:
            for i in self._nbrs[j]:
                r = int(i) // self.block
                if r != rank:
                    peers.add(r)
        self.peers = peers
        self._connect(endpoints)

    def owner(self, agent: int) -> int:
        return int(agent) // self.block

    def _connect(self, endpoints: dict[int, tuple[str, int]]) -> None:
        # Deterministic handshake: lower rank accepts, higher connects.
        for r in sorted(p for p in self.peers if p > self.rank):
            s = socket.create_connection(tuple(endpoints[r]),
                                         timeout=self.timeout)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.sendall(_HELLO.pack(self.rank))
            self._socks[r] = s
        expected = {p for p in self.peers if p < self.rank}
        self._listen.settimeout(self.timeout)
        while expected:
            conn, _ = self._listen.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            hello = _recv_exact(conn, _HELLO.size)
            if hello is None:
                continue
            (r,) = _HELLO.unpack(hello)
            self._socks[r] = conn
            expected.discard(r)

    def mark_dead(self, rank: int) -> None:
        """Control-plane death notice (e.g. from the launcher): stop
        expecting frames from this peer and close its channel."""
        if rank in self.dead_ranks:
            return
        self.dead_ranks.add(rank)
        s = self._socks.pop(rank, None)
        if s is not None:
            try:
                s.close()
            except OSError:
                pass

    def _send(self, r: int, payload: bytes) -> None:
        if r in self.dead_ranks:
            return
        try:
            self._socks[r].sendall(payload)
        except (KeyError, ConnectionError, OSError):
            self.mark_dead(r)

    def _pump(self, owed: dict[int, int]) -> None:
        """Drain frames from peers until nothing is owed (or owing peers
        die/time out).  Out-of-step frames (a peer running ahead) are
        buffered for their step.  Does NOT count drops — `exchange` owns
        that counter and tallies what is actually missing at accumulate
        time."""
        import time as _t
        t0 = _t.monotonic()
        deadline = t0 + self.timeout
        try:
            self._pump_inner(owed, deadline)
        finally:
            self.comm_wait_s += _t.monotonic() - t0

    def _pump_inner(self, owed: dict[int, int], deadline: float) -> None:
        import time as _t
        while any(n > 0 for n in owed.values()):
            socks = {self._socks[r]: r for r, n in owed.items()
                     if n > 0 and r not in self.dead_ranks
                     and r in self._socks}
            if not socks:
                for r, n in owed.items():
                    owed[r] = 0
                return
            wait = max(0.0, deadline - _t.monotonic())
            ready, _, _ = select.select(list(socks), [], [], min(wait, 1.0))
            if not ready:
                if _t.monotonic() >= deadline:
                    for s, r in socks.items():
                        self.mark_dead(r)
                continue
            for s in ready:
                r = socks[s]
                hdr = _recv_exact(s, FRAME_HEADER.size)
                if hdr is None:
                    self.mark_dead(r)
                    continue
                fstep, sender, receiver, nbytes = FRAME_HEADER.unpack(hdr)
                body = _recv_exact(s, nbytes)
                if body is None:
                    self.mark_dead(r)
                    continue
                if self.secret is not None:
                    # A truncated tag is indistinguishable from a dead
                    # peer; a present-but-wrong tag is a tampered or
                    # cross-run frame.  Either way the channel is no
                    # longer trustworthy — kill it, never buffer the v.
                    tag = _recv_exact(s, WIRE_TAG_SIZE)
                    want = hmac.new(self.secret, hdr + body,
                                    hashlib.sha256).digest()
                    if tag is None or not hmac.compare_digest(tag, want):
                        self.tag_failures += 1
                        self.mark_dead(r)
                        continue
                self._rbuf[(fstep, sender, receiver)] = np.frombuffer(
                    body, dtype=np.float32).copy()
                if owed.get(r, 0) > 0:
                    owed[r] -= 1

    def exchange(self, x_local, u_local, W, B, *, step: int = 0,
                 capture: bool = False):
        x = np.asarray(x_local, dtype=np.float32)
        u = np.asarray(u_local, dtype=np.float32)
        W = np.asarray(W, dtype=np.float32)
        B = np.asarray(B, dtype=np.float32)
        L, lo = self.block, self.local_lo
        if x.shape[0] != L:
            raise ValueError(f"rank {self.rank} owns {L} agents, got "
                             f"{x.shape[0]} rows")
        # Sender side: every outgoing column computed once (also the
        # capture record); remote rows are framed onto the wire.
        cols = capture_columns(W, B, x, u, lo=lo)  # (m, L, D)
        for l, j in enumerate(range(lo, lo + L)):
            for i in self._nbrs[j]:
                r = self.owner(i)
                if r == self.rank:
                    continue
                payload = cols[int(i), l].tobytes()
                frame = FRAME_HEADER.pack(step, j, int(i),
                                          len(payload)) + payload
                if self.secret is not None:
                    frame += hmac.new(self.secret, frame,
                                      hashlib.sha256).digest()
                if self.audit_wire:
                    self.sent_frames.append(frame)
                self._send(r, frame)
        # Receive everything owed for this step.
        owed: dict[int, int] = {}
        for i in self.local_agents:
            for j in self._nbrs[i]:
                r = self.owner(j)
                if r != self.rank and r not in self.dead_ranks:
                    key = (step, int(j), int(i))
                    if key not in self._rbuf:
                        owed[r] = owed.get(r, 0) + 1
        self._pump(owed)
        # Canonical accumulation per local receiver.
        out = np.empty_like(x)
        for l, i in enumerate(range(lo, lo + L)):
            contribs: dict[int, np.ndarray] = {}
            for j in self._nbrs[i]:
                j = int(j)
                if self.owner(j) == self.rank:
                    contribs[j] = link_message(W[i, j], B[i, j],
                                               x[j - lo], u[j - lo])
                else:
                    v = self._rbuf.pop((step, j, i), None)
                    if v is not None:
                        contribs[j] = v
                    else:
                        # The one place drops are counted: a needed remote
                        # contribution that never arrived, whatever the
                        # reason (peer died mid-pump, or was dead before
                        # the step started).
                        self.drops += 1
            out[l] = accumulate(
                i, link_message(W[i, i], B[i, i], x[l], u[l]), contribs)
        if not capture:
            return out
        return out, cols

    def close(self) -> None:
        for s in list(self._socks.values()):
            try:
                s.close()
            except OSError:
                pass
        self._socks.clear()
        try:
            self._listen.close()
        except OSError:
            pass


class PipelinedSocketTransport(SocketTransport):
    """`SocketTransport` with the comm/compute overlap the blocking
    exchange leaves on the table — same wire protocol (frame layout,
    HMAC, handshake), bit-identical trajectories.

    What changes and why it is faster:

    * **Lazy per-link staging.**  The blocking exchange materializes the
      dense `capture_columns` tensor — (m, L) rows including every
      non-edge — and then RECOMPUTES each local link's message in the
      accumulate loop.  Here each realized link's ``v`` row is computed
      exactly once (`link_message`, eagerly — the bit-parity contract)
      and reused for both the wire and the local accumulation.
    * **Send thread + bounded outbox.**  Frames are enqueued as
      (header, payload-memoryview, tag) scatter-gather triples — zero
      user-space copies — and a daemon thread drains them with
      ``sendmsg`` while the caller moves on to the accumulate loop (and,
      with ``frames_ahead``, the next step's gradient/obfuscate
      compute).  The outbox holds at most ``outbox_frames`` frames:
      a slow or stalled peer exerts backpressure on `exchange` instead
      of buffering unboundedly.
    * **Eager receive thread.**  A select loop drains peer sockets into
      ``_rbuf`` the moment frames arrive (``recv_into`` a preallocated
      array, streaming HMAC), so a peer's step-k frames are typically
      already buffered when our step-k accumulate asks for them.
    * **``frames_ahead`` window.**  `exchange(step=k)` first waits until
      ``k - (newest_step_sent_by_slowest_live_peer + 1) <= frames_ahead``
      — with 0 every rank stays in lockstep with its slowest peer; with
      f > 0 a rank may run up to f steps ahead (its sends buffer on the
      peer side) before blocking, which is what absorbs stragglers.

    Wait time on both gates accumulates into ``comm_wait_s``; ``drops``
    keeps the `exchange`-owned accounting of the base class.

    ``capture=True`` falls back to the dense `capture_columns` tensor
    for the returned record (the audit path wants the full column block;
    entry-for-entry the same math as the staged rows).
    """

    def __init__(self, *args, outbox_frames: int = 64,
                 frames_ahead: int = 1, **kwargs):
        if outbox_frames < 1:
            raise ValueError(f"outbox_frames must be >= 1, got "
                             f"{outbox_frames}")
        if frames_ahead < 0:
            raise ValueError(f"frames_ahead must be >= 0, got "
                             f"{frames_ahead}")
        self.frames_ahead = frames_ahead
        self._outbox: queue.Queue = queue.Queue(outbox_frames)
        self._cv = threading.Condition()
        self._peer_step: dict[int, int] = {}
        self._stopping = False
        super().__init__(*args, **kwargs)
        for s in self._socks.values():
            s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4 << 20)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4 << 20)
        self._tx = threading.Thread(target=self._send_loop, daemon=True)
        self._rx = threading.Thread(target=self._recv_loop, daemon=True)
        self._tx.start()
        self._rx.start()

    def _mark_dead_notify(self, rank: int) -> None:
        with self._cv:
            self.mark_dead(rank)
            self._cv.notify_all()

    def _send_loop(self) -> None:
        while True:
            try:
                item = self._outbox.get(timeout=0.2)
            except queue.Empty:
                if self._stopping:
                    return
                continue
            if item is None:
                return
            r, bufs = item
            if r in self.dead_ranks:
                continue
            try:
                s = self._socks[r]
                mvs = [b if isinstance(b, memoryview)
                       else memoryview(b) for b in bufs]
                while mvs:
                    sent = s.sendmsg(mvs)
                    while mvs and sent >= len(mvs[0]):
                        sent -= len(mvs[0])
                        mvs.pop(0)
                    if mvs and sent:
                        mvs[0] = mvs[0][sent:]
            except (KeyError, ConnectionError, OSError):
                self._mark_dead_notify(r)

    def _recv_loop(self) -> None:
        while not self._stopping:
            socks = {s: r for r, s in list(self._socks.items())
                     if r not in self.dead_ranks}
            if not socks:
                time.sleep(0.01)
                continue
            try:
                ready, _, _ = select.select(list(socks), [], [], 0.2)
            except (OSError, ValueError):
                continue  # a socket closed under us; re-snapshot
            for s in ready:
                r = socks[s]
                hdr = _recv_exact(s, FRAME_HEADER.size)
                if hdr is None:
                    self._mark_dead_notify(r)
                    continue
                fstep, sender, receiver, nbytes = FRAME_HEADER.unpack(hdr)
                vec = np.empty(nbytes // 4, dtype=np.float32)
                mv = memoryview(vec).cast("B")
                got, ok = 0, True
                while got < nbytes:
                    try:
                        n = s.recv_into(mv[got:], nbytes - got)
                    except (ConnectionError, OSError):
                        n = 0
                    if n == 0:
                        ok = False
                        break
                    got += n
                if not ok:
                    self._mark_dead_notify(r)
                    continue
                if self.secret is not None:
                    tag = _recv_exact(s, WIRE_TAG_SIZE)
                    h = hmac.new(self.secret, hdr, hashlib.sha256)
                    h.update(mv)
                    if tag is None or not hmac.compare_digest(
                            tag, h.digest()):
                        self.tag_failures += 1
                        self._mark_dead_notify(r)
                        continue
                with self._cv:
                    self._rbuf[(fstep, sender, receiver)] = vec
                    self._peer_step[r] = max(
                        self._peer_step.get(r, -1), fstep)
                    self._cv.notify_all()

    def exchange(self, x_local, u_local, W, B, *, step: int = 0,
                 capture: bool = False):
        x = np.asarray(x_local, dtype=np.float32)
        u = np.asarray(u_local, dtype=np.float32)
        W = np.asarray(W, dtype=np.float32)
        B = np.asarray(B, dtype=np.float32)
        L, lo = self.block, self.local_lo
        if x.shape[0] != L:
            raise ValueError(f"rank {self.rank} owns {L} agents, got "
                             f"{x.shape[0]} rows")
        # frames_ahead gate: don't outrun the slowest live peer's observed
        # sends by more than the window.
        t0 = time.monotonic()
        deadline = t0 + self.timeout
        with self._cv:
            while True:
                live = [r for r in self.peers if r not in self.dead_ranks]
                if not live:
                    break
                slowest = min(self._peer_step.get(r, -1) for r in live)
                if step - (slowest + 1) <= self.frames_ahead:
                    break
                if time.monotonic() >= deadline:
                    break  # a silently-stalled peer; the needed-frames
                           # wait below owns the final timeout/drop call
                self._cv.wait(0.1)
        self.comm_wait_s += time.monotonic() - t0
        # Lazy per-link staging: only realized links are computed, each
        # row exactly once, reused by the accumulate loop below.  Eager
        # numpy ops — same bit-parity contract as the blocking path.
        staged: dict[tuple[int, int], np.ndarray] = {}
        for l, j in enumerate(range(lo, lo + L)):
            for i in self._nbrs[j]:
                i = int(i)
                row = link_message(W[i, j], B[i, j], x[l], u[l])
                staged[(j, i)] = row
                r = self.owner(i)
                if r == self.rank:
                    continue
                hdr = FRAME_HEADER.pack(step, j, i, row.nbytes)
                bufs: list = [hdr, memoryview(row).cast("B")]
                if self.secret is not None:
                    h = hmac.new(self.secret, hdr, hashlib.sha256)
                    h.update(bufs[1])
                    bufs.append(h.digest())
                if self.audit_wire:
                    self.sent_frames.append(b"".join(bytes(b)
                                                     for b in bufs))
                # Bounded: blocks (backpressure) when outbox_frames
                # frames are already in flight.
                self._outbox.put((r, bufs))
        # Wait for everything a local agent needs this step.
        needed = [(step, int(j), int(i))
                  for i in self.local_agents for j in self._nbrs[i]
                  if self.owner(int(j)) != self.rank]
        t0 = time.monotonic()
        deadline = t0 + self.timeout
        with self._cv:
            while True:
                missing = [k for k in needed if k not in self._rbuf
                           and self.owner(k[1]) not in self.dead_ranks]
                if not missing or time.monotonic() >= deadline:
                    break
                self._cv.wait(0.2)
        self.comm_wait_s += time.monotonic() - t0
        # Canonical accumulation per local receiver, staged rows reused.
        out = np.empty_like(x)
        with self._cv:
            for l, i in enumerate(range(lo, lo + L)):
                contribs: dict[int, np.ndarray] = {}
                for j in self._nbrs[i]:
                    j = int(j)
                    if self.owner(j) == self.rank:
                        contribs[j] = staged[(j, i)]
                    else:
                        v = self._rbuf.pop((step, j, i), None)
                        if v is not None:
                            contribs[j] = v
                        else:
                            self.drops += 1
                out[l] = accumulate(
                    i, link_message(W[i, i], B[i, i], x[l], u[l]), contribs)
        if not capture:
            return out
        return out, capture_columns(W, B, x, u, lo=lo)

    def close(self) -> None:
        self._stopping = True
        try:
            self._outbox.put_nowait(None)
        except queue.Full:
            pass
        for t in (getattr(self, "_tx", None), getattr(self, "_rx", None)):
            if t is not None and t.is_alive():
                t.join(timeout=2.0)
        super().close()
