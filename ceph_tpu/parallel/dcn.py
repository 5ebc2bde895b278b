"""Multi-process DCN tier: jax multi-controller hosts under the
socket messenger's control plane.

The reference scales past one host with AsyncMessenger carrying
MOSDECSubOpWrite/Read between OSD processes over the data-center
network (msg/async/AsyncMessenger.h:95, ProtocolV2.h:13; SURVEY.md
§5.8 maps that stack to ICI + DCN). The TPU-native equivalent built
here:

- N OS processes ("hosts"), each owning a slice of ONE global
  ``jax.sharding.Mesh`` via ``jax.distributed.initialize`` (the jax
  multi-controller model). Workers pin themselves to the CPU backend
  with gloo collectives: this tier is a CPU SIMULATION of the
  multi-host layout and has never run on TPU hosts — it is not
  multi-host evidence.
- The mesh is laid out so ``dp`` (stripe batch) is intra-host and
  ``sp`` (the EC shard axis) SPANS hosts: the XOR-reduction that
  combines parity — ring reduce-scatter + all-gather in
  parallel/collectives.ring_parity — runs its ppermute hops ACROSS
  host boundaries, i.e. the shard fan-out travels as XLA collectives
  over DCN, not as application-level sends.
- The repo's framed socket messenger carries the CONTROL plane: the
  coordinator broadcasts identical op metadata to every host (the
  SPMD multi-controller discipline) with each host's own shard-slice
  payload — the per-shard sub-op fan-out of MOSDECSubOpWrite mapped
  onto hosts — and hosts answer with their locally-addressable result
  shards plus their ``ec_dispatch`` counter deltas, so the mesh route
  stays counter-verified end to end.

Coordinator (``DcnCluster``) runs in the caller's process and does
NOT join the jax cluster; workers are spawned as subprocesses running
``python -m ceph_tpu.parallel.dcn``. CI drives a 2-host x 2-device
cluster (tests/test_dcn.py); ``__graft_entry__.dryrun_multichip``
runs the same pass and reports ``hosts>1`` in its tail line.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np

HELLO_TIMEOUT = 90.0
OP_TIMEOUT = 180.0


# ---------------------------------------------------------------- worker
def _worker_main(argv: list[str]) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--nprocs", type=int, required=True)
    ap.add_argument("--coord", required=True)   # jax coordinator addr
    ap.add_argument("--devices", type=int, required=True)  # per host
    ap.add_argument("--ctrl", required=True)    # messenger host:port
    args = ap.parse_args(argv)

    # Platform pinning BEFORE any backend initializes: the DCN tier
    # is a CPU simulation by construction (virtual CPU devices, gloo
    # collectives between OS processes) — a worker never takes a chip.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=args.coord,
        num_processes=args.nprocs,
        process_id=args.rank,
    )
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ceph_tpu.codecs.matrix_codec import _dispatch_counters
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.msg.messages import DcnCmd, DcnHello, DcnReply
    from ceph_tpu.msg.messenger import Messenger
    from ceph_tpu.parallel import dispatch as mesh_dispatch
    from ceph_tpu.utils import config

    devs = jax.devices()
    # sp SPANS processes: global device list is process-major, so the
    # transpose puts one device of EVERY process in each mesh row —
    # column j == host j. dp stays intra-host.
    mesh = Mesh(
        np.array(devs).reshape(args.nprocs, args.devices).T,
        ("dp", "sp"),
    )
    config.set("ec_use_mesh", True)
    mesh_dispatch.set_mesh(mesh)

    msgr = Messenger(f"dcn-host-{args.rank}")
    done = threading.Event()

    def snap():
        pc = _dispatch_counters()
        return {kk: pc.get(kk) for kk in pc.dump()}

    codecs: dict[tuple, object] = {}

    def get_codec(meta: dict):
        """One codec instance per (plugin, profile) for the worker's
        lifetime — keeps the DecodeTableCache warm across commands
        (rebuilding per op would re-invert decode matrices every
        time, the exact cost the ISA TableCache precedent avoids)."""
        key = (meta["plugin"], tuple(sorted(meta["profile"].items())))
        if key not in codecs:
            codecs[key] = registry.factory(
                meta["plugin"], dict(meta["profile"])
            )
        return codecs[key]

    def run_cmd(cmd: DcnCmd) -> DcnReply:
        from ceph_tpu.codecs.bitmatrix_codec import BitMatrixCodec

        meta = cmd.meta
        if cmd.kind == "shutdown":
            done.set()
            return DcnReply(cmd.tid, args.rank, {"ok": True})
        if cmd.kind == "apply":
            return _run_apply(cmd)
        codec = get_codec(meta)
        b, c, n = meta["shape"]
        sp = mesh.shape["sp"]
        local = np.frombuffer(cmd.payload, np.uint8).reshape(
            b, c // sp, n
        )
        # Packet codes (liberation family) dispatch at PACKET
        # granularity: each host packetizes its own chunk block (a
        # chunk's w packets stay host-local, so the sp split is
        # preserved: c_blk chunks -> c_blk*w packets).
        packets = isinstance(codec, BitMatrixCodec)
        if packets:
            w = codec.w
            local = local.reshape(b, (c // sp) * w, n // w)
            gshape = (b, c * w, n // w)
        else:
            gshape = (b, c, n)
        sharding = NamedSharding(mesh, P("dp", "sp", None))
        stacked = jax.make_array_from_process_local_data(
            sharding, local, gshape
        )
        before = snap()
        # the bitmatrix goes in as HOST numpy: under multi-controller,
        # identical numpy inputs are valid replicated operands, while
        # a jnp array committed to one process's device 0 is not a
        # legal input for a mesh spanning processes
        if cmd.kind == "encode":
            bm_np = codec._encode_bmat_np
        elif cmd.kind == "decode":
            present = list(meta["present"])
            want = list(meta["want"])
            key = (tuple(present), tuple(want))
            if packets:
                dec01 = codec._host_tables.get(
                    key,
                    lambda: codec._build_decode_bitmatrix(present, want),
                )
                bm_np, _key = codec._host_bits(dec01)
            else:
                bm_np = codec._tables.get(
                    key, lambda: codec._build_decode_bmat(present, want)
                )
        else:
            raise ValueError(f"unknown DCN op {cmd.kind!r}")
        out = codec._dispatch_bitmatrix(bm_np, bm_np, stacked, cmd.kind)
        delta = {
            kk: v - before.get(kk, 0)
            for kk, v in snap().items()
            if v != before.get(kk, 0)
        }
        # The output is replicated over sp (out_specs P("dp", ...)):
        # this host's addressable shards cover the WHOLE result — but
        # the coordinator reads only rank 0's copy, so nonzero ranks
        # ACK with metadata after syncing (the _run_apply discipline;
        # shipping (n_hosts-1)x the output bytes bought nothing).
        if args.rank == 0:
            full = _assemble_addressable(out)
            if packets:  # de-packetize on the host copy
                full = full.reshape(b, full.shape[1] // codec.w, n)
            return DcnReply(
                cmd.tid, args.rank,
                {"ok": True, "counters": delta,
                 "shape": list(full.shape), "hosts": args.nprocs},
                full.tobytes(),
            )
        out.block_until_ready()
        oshape = [b, out.shape[1] // codec.w, n] if packets else [
            b, out.shape[1], out.shape[2]
        ]
        return DcnReply(
            cmd.tid, args.rank,
            {"ok": True, "counters": delta, "shape": oshape,
             "hosts": args.nprocs},
        )

    def _run_apply(cmd: DcnCmd) -> DcnReply:
        """Raw bitmatrix application — the generic engine op the codec
        dispatch route ships over DCN (encode, decode and delta all
        reduce to it; the payload is bitmatrix bytes + this host's
        shard-slice)."""
        meta = cmd.meta
        r8, c8 = meta["bm_shape"]
        bm_bytes = c8 * r8
        bm_np = np.frombuffer(
            cmd.payload[:bm_bytes], np.uint8
        ).reshape(r8, c8)
        b, c, n = meta["shape"]
        sp = mesh.shape["sp"]
        local = np.frombuffer(
            cmd.payload[bm_bytes:], np.uint8
        ).reshape(b, c // sp, n)
        sharding = NamedSharding(mesh, P("dp", "sp", None))
        stacked = jax.make_array_from_process_local_data(
            sharding, local, (b, c, n)
        )
        out = mesh_dispatch.mesh_apply_bitmatrix(mesh, bm_np, stacked)
        # every rank holds the full (sp-replicated) output, but the
        # coordinator reads only rank 0's copy — the others ACK with
        # metadata so (n_hosts-1) x output bytes never cross the wire
        if args.rank == 0:
            full = _assemble_addressable(out)
            return DcnReply(
                cmd.tid, args.rank,
                {"ok": True, "shape": list(full.shape),
                 "hosts": args.nprocs, "counters": {}},
                full.tobytes(),
            )
        out.block_until_ready()
        return DcnReply(
            cmd.tid, args.rank,
            {"ok": True, "shape": list(out.shape),
             "hosts": args.nprocs, "counters": {}},
        )

    def dispatch(c, msg) -> None:
        if isinstance(msg, DcnCmd):
            try:
                reply = run_cmd(msg)
            except Exception as e:  # surfaced to the coordinator
                reply = DcnReply(
                    msg.tid, args.rank,
                    {"ok": False, "error": f"{type(e).__name__}: {e}"},
                )
            c.send(reply)

    # dispatcher installed BEFORE connecting: the coordinator may send
    # the first command the moment it sees the hello
    msgr.set_dispatcher(dispatch)
    host, port = args.ctrl.rsplit(":", 1)
    conn = msgr.connect((host, int(port)))
    conn.send(DcnHello(
        args.rank, args.nprocs, len(jax.local_devices()), len(devs)
    ))
    while not done.wait(0.2):
        pass
    time.sleep(0.2)  # let the shutdown reply flush
    msgr.shutdown()


def _assemble_addressable(arr) -> np.ndarray:
    """Reassemble a global jax.Array from THIS process's addressable
    shards (valid when the process's shards cover every global index,
    e.g. outputs replicated over the cross-host axis)."""
    out = np.zeros(arr.shape, arr.dtype)
    seen = np.zeros(arr.shape, bool)
    for shard in arr.addressable_shards:
        out[shard.index] = np.asarray(shard.data)
        seen[shard.index] = True
    if not seen.all():
        raise ValueError(
            "output not fully addressable on this host — cross-host "
            "sharding left gaps"
        )
    return out


# ------------------------------------------------------------ coordinator
class DcnCluster:
    """Spawn + drive N jax multi-controller host processes.

    The coordinator stays OUTSIDE the jax cluster (it may already own
    a different backend — a TPU chip, a test's CPU mesh); it talks
    to the hosts purely over the messenger control plane.
    """

    def __init__(self, n_hosts: int = 2, devices_per_host: int = 2) -> None:
        self.n_hosts = n_hosts
        self.devices_per_host = devices_per_host
        self.procs: list[subprocess.Popen] = []
        self._errfiles: list = []
        #: tids with a waiter: replies for anything else (stragglers
        #: after a timeout) are dropped at arrival instead of
        #: accumulating payload bytes forever
        self._awaiting: set[int] = set()
        self.conns: dict[int, object] = {}
        self.hellos: dict[int, object] = {}
        self._replies: dict[tuple[int, int], object] = {}
        self._lock = threading.Lock()
        #: serializes WHOLE ops (send fan-out through reply wait):
        #: every op bottoms out in a cross-host SPMD collective, which
        #: requires all hosts to execute ops in the SAME order —
        #: interleaved sends from concurrent threads give the hosts
        #: divergent orders and their collectives pair wrongly (hangs
        #: observed under a 12-thread stress test). Workers execute
        #: serially anyway, so this lock costs no real parallelism.
        self._op_lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._tid = 0
        self.msgr = None

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "DcnCluster":
        from ceph_tpu.msg.messages import DcnHello, DcnReply
        from ceph_tpu.msg.messenger import Messenger

        self.msgr = Messenger("dcn-coordinator")
        addr = self.msgr.bind("127.0.0.1", 0)

        def dispatch(conn, msg) -> None:
            with self._cv:
                if isinstance(msg, DcnHello):
                    self.hellos[msg.rank] = msg
                    self.conns[msg.rank] = conn
                elif isinstance(msg, DcnReply):
                    if msg.tid in self._awaiting:
                        self._replies[(msg.tid, msg.rank)] = msg
                    # else: straggler after a timeout — drop it
                self._cv.notify_all()

        self.msgr.set_dispatcher(dispatch)

        import tempfile

        coord_port = _free_port()
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)  # workers pin their own
        for rank in range(self.n_hosts):
            # worker stderr lands in a temp file so a startup failure
            # (gloo/jax.distributed init, port clash) keeps its
            # traceback — DEVNULL made those undiagnosable
            errf = tempfile.NamedTemporaryFile(
                mode="w+", prefix=f"dcn-host{rank}-", suffix=".err",
                delete=False,
            )
            self._errfiles.append(errf)
            self.procs.append(subprocess.Popen(
                [
                    sys.executable, "-m", "ceph_tpu.parallel.dcn",
                    "--rank", str(rank),
                    "--nprocs", str(self.n_hosts),
                    "--coord", f"127.0.0.1:{coord_port}",
                    "--devices", str(self.devices_per_host),
                    "--ctrl", f"{addr[0]}:{addr[1]}",
                ],
                env=env,
                stdout=subprocess.DEVNULL,
                stderr=errf,
            ))
        deadline = time.monotonic() + HELLO_TIMEOUT
        failed = False
        with self._cv:
            while len(self.hellos) < self.n_hosts:
                left = deadline - time.monotonic()
                if left <= 0 or any(
                    p.poll() is not None for p in self.procs
                ):
                    failed = True
                    break
                self._cv.wait(min(left, 0.5))
        if failed:
            # OUTSIDE the cv: stop() -> _wait() re-acquires it (a
            # plain Lock — calling under the cv deadlocked forever on
            # partial startup)
            got = len(self.hellos)
            tails = self._stderr_tails()
            self.stop()
            raise RuntimeError(
                f"DCN hosts failed to start ({got}/{self.n_hosts} "
                f"hellos); worker stderr tails: {tails}"
            )
        return self

    def _stderr_tails(self, limit: int = 800) -> dict[int, str]:
        tails = {}
        for rank, f in enumerate(self._errfiles):
            try:
                f.flush()
                with open(f.name) as fh:
                    tails[rank] = fh.read()[-limit:]
            except Exception:
                pass
        return tails

    def stop(self) -> None:
        from ceph_tpu.msg.messages import DcnCmd

        try:
            if self.conns:
                tid = self._next_tid()
                for conn in self.conns.values():
                    conn.send(DcnCmd(tid, "shutdown", {}))
                self._wait(tid, timeout=5.0, strict=False)
        except Exception:
            pass
        for p in self.procs:
            try:
                p.terminate()
            except Exception:
                pass
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        if self.msgr is not None:
            self.msgr.shutdown()
        for f in self._errfiles:
            try:
                f.close()
                os.unlink(f.name)
            except Exception:
                pass
        self._errfiles = []

    def __enter__(self) -> "DcnCluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- ops -----------------------------------------------------------
    def _next_tid(self) -> int:
        # under the lock: OSD daemons dispatch from multiple reader
        # threads — a raced tid would cross-deliver replies. The tid
        # registers as awaited HERE, before any send, so a fast reply
        # can never race past the filter in the dispatcher.
        with self._lock:
            self._tid += 1
            self._awaiting.add(self._tid)
            return self._tid

    def _wait(self, tid: int, timeout: float = OP_TIMEOUT,
              strict: bool = True) -> dict[int, object]:
        deadline = time.monotonic() + timeout
        with self._cv:
            try:
                while True:
                    got = {
                        r: self._replies[(tid, r)]
                        for r in range(self.n_hosts)
                        if (tid, r) in self._replies
                    }
                    if len(got) == self.n_hosts:
                        return got
                    left = deadline - time.monotonic()
                    if left <= 0:
                        if strict:
                            raise TimeoutError(
                                f"DCN op {tid}: {len(got)}/"
                                f"{self.n_hosts} replies"
                            )
                        return got
                    self._cv.wait(min(left, 0.5))
            finally:
                # consume on EVERY exit (complete, timeout, raise):
                # replies carry whole output payloads — leaking them
                # per-op would grow without bound on the codec
                # dispatch hot path, and un-awaited stragglers are
                # dropped at arrival
                self._awaiting.discard(tid)
                for r in range(self.n_hosts):
                    self._replies.pop((tid, r), None)

    def _run(self, kind: str, plugin: str, profile: dict,
             data: np.ndarray, meta_extra: dict | None = None):
        """Broadcast one op: identical metadata to every host, each
        host carrying its own sp-block of the shard axis."""
        with self._op_lock:
            return self._run_locked(kind, plugin, profile, data, meta_extra)

    def _run_locked(self, kind, plugin, profile, data, meta_extra=None):
        from ceph_tpu.msg.messages import DcnCmd

        b, c, n = data.shape
        sp = self.n_hosts
        if c % sp:
            raise ValueError(f"shard axis {c} must divide hosts {sp}")
        tid = self._next_tid()
        meta = {
            "plugin": plugin, "profile": profile,
            "shape": [b, c, n], **(meta_extra or {}),
        }
        blk = c // sp
        for rank, conn in self.conns.items():
            slice_ = np.ascontiguousarray(
                data[:, rank * blk : (rank + 1) * blk, :]
            )
            conn.send(DcnCmd(tid, kind, meta, slice_.tobytes()))
        replies = self._wait(tid)
        for r, rep in sorted(replies.items()):
            if not rep.meta.get("ok"):
                raise RuntimeError(
                    f"DCN host {r}: {rep.meta.get('error')}"
                )
        rep0 = replies[0]
        out = np.frombuffer(rep0.payload, np.uint8).reshape(
            rep0.meta["shape"]
        )
        counters = {
            r: rep.meta["counters"] for r, rep in replies.items()
        }
        return out, counters

    def supported(self, bm_shape, data_shape) -> bool:
        """Divisibility contract for the generic apply route: the
        shard axis must split across hosts, the bitmatrix must match
        it, and the stripe batch must split over each host's devices
        — directly or by folding the lane axis (the same exactness
        argument as mesh_apply_bitmatrix: the GF(2) apply is
        independent per lane)."""
        if len(data_shape) != 3:
            return False
        b, c, n = data_shape
        dp = self.devices_per_host
        return (
            c % self.n_hosts == 0
            and bm_shape[1] == c * 8
            and (b % dp == 0 or n % dp == 0)
        )

    def apply_bitmatrix(
        self, bm_np: np.ndarray, data: np.ndarray,
        timeout: float = 60.0,
    ):
        """Generic [R*8, C*8] bitmatrix over [B, C, N] host data,
        fanned across hosts (the engine-route op: encode, decode and
        parity delta all arrive here when the codec dispatch routes
        over DCN). Shorter timeout than the command ops: this sits on
        the data path, where a dead host should fail fast into the
        dispatcher's fallback."""
        with self._op_lock:
            return self._apply_bitmatrix_locked(bm_np, data, timeout)

    def _apply_bitmatrix_locked(self, bm_np, data, timeout):
        from ceph_tpu.msg.messages import DcnCmd

        b0, c, n0 = data.shape
        dp = self.devices_per_host
        fold = b0 % dp != 0
        if fold:
            # batch-1 deltas and odd stripe batches: fold the lane
            # axis into the batch so dp divides it (exact — the
            # bitmatrix apply is lane-independent)
            if n0 % dp:
                raise ValueError(
                    f"batch {b0} and lanes {n0} both unsplittable by "
                    f"dp={dp}"
                )
            data = (
                data.reshape(b0, c, dp, n0 // dp)
                .transpose(0, 2, 1, 3)
                .reshape(b0 * dp, c, n0 // dp)
            )
        b, c, n = data.shape
        sp = self.n_hosts
        if c % sp:
            raise ValueError(f"shard axis {c} must divide hosts {sp}")
        tid = self._next_tid()
        meta = {
            "bm_shape": [int(bm_np.shape[0]), int(bm_np.shape[1])],
            "shape": [b, c, n],
        }
        bm_bytes = np.ascontiguousarray(bm_np, np.uint8).tobytes()
        blk = c // sp
        for rank, conn in self.conns.items():
            slice_ = np.ascontiguousarray(
                data[:, rank * blk : (rank + 1) * blk, :]
            )
            conn.send(DcnCmd(
                tid, "apply", meta, bm_bytes + slice_.tobytes()
            ))
        replies = self._wait(tid, timeout=timeout)
        for r, rep in sorted(replies.items()):
            if not rep.meta.get("ok"):
                raise RuntimeError(
                    f"DCN host {r}: {rep.meta.get('error')}"
                )
        rep0 = replies[0]
        out = np.frombuffer(rep0.payload, np.uint8).reshape(
            rep0.meta["shape"]
        )
        if fold:
            r_out = out.shape[1]
            out = (
                out.reshape(b0, dp, r_out, n)
                .transpose(0, 2, 1, 3)
                .reshape(b0, r_out, n0)
            )
        return out

    def encode(self, plugin: str, profile: dict, data: np.ndarray):
        """[B, k, N] data -> ([B, m, N] parity, per-host counters)."""
        return self._run("encode", plugin, profile, data)

    def decode(self, plugin: str, profile: dict, present: list[int],
               want: list[int], survivors: np.ndarray):
        """[B, len(present), N] survivors -> [B, len(want), N]."""
        return self._run(
            "decode", plugin, profile, survivors,
            {"present": list(present), "want": list(want)},
        )


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


if __name__ == "__main__":
    _worker_main(sys.argv[1:])
