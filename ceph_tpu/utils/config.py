"""Declarative, layered configuration — the Option/ConfigProxy analog.

Mirrors the reference's config system (common/options/*.yaml.in
declarative Option specs code-generated by y2c.py; layered resolution
in common/config.cc): options are declared once with type, default,
bounds/enum, and description; effective values resolve through layers

    compiled default < config file < environment < runtime override

(the reference adds the mon config database between file and env; here
the file layer plays that role). Observers are notified when a key's
effective value changes (common/config_obs.h).

Environment variables use the ``CEPH_TPU_`` prefix with the option name
upper-cased, e.g. ``CEPH_TPU_EC_STRIPE_BATCH=16``.
"""

from __future__ import annotations

import json
import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field

ENV_PREFIX = "CEPH_TPU_"

# Layer precedence, low to high (config.cc layered resolution).
#: precedence, lowest to highest. "mon" is the ConfigMonitor analog's
#: layer: the mon-replicated central db overrides compiled defaults
#: but local file/env/runtime settings override IT (the reference's
#: local-emergency-override ordering, doc/rados/configuration).
LAYERS = ("default", "mon", "file", "env", "runtime")


@dataclass(frozen=True)
class Option:
    """One declared option (the Option type of common/options.h)."""

    name: str
    type: type  # int | float | bool | str
    default: object
    desc: str = ""
    level: str = "advanced"  # basic | advanced | dev
    min: float | None = None
    max: float | None = None
    enum_values: tuple[str, ...] = ()
    see_also: tuple[str, ...] = ()

    def parse(self, raw):
        if isinstance(raw, str) and self.type is not str:
            if self.type is bool:
                val = raw.strip().lower() in ("1", "true", "yes", "on")
            else:
                val = self.type(raw)
        else:
            val = self.type(raw)
        self.validate(val)
        return val

    def validate(self, val) -> None:
        if self.min is not None and val < self.min:
            raise ValueError(f"{self.name}={val} below min {self.min}")
        if self.max is not None and val > self.max:
            raise ValueError(f"{self.name}={val} above max {self.max}")
        if self.enum_values and val not in self.enum_values:
            raise ValueError(
                f"{self.name}={val!r} not one of {self.enum_values}"
            )


# The framework's option schema. Names follow the reference's where a
# direct analog exists (osd_pool_default_erasure_code_profile,
# bluestore_csum_type, ...), ceph_tpu-specific axes otherwise.
OPTIONS: tuple[Option, ...] = (
    Option(
        "erasure_code_default_plugin", str, "isa",
        desc="plugin used when a profile names none "
             "(osd_pool_default_erasure_code_profile analog)",
        enum_values=("isa", "jerasure", "lrc", "shec", "clay"),
        level="basic",
    ),
    Option(
        "erasure_code_default_profile", str, "k=2 m=2",
        desc="default EC profile key=value pairs",
        level="basic",
    ),
    Option(
        "ec_extent_cache_lines", int, 1024,
        desc="LRU lines kept by the write-back extent cache "
             "(ECExtentCache LRU analog)", min=0,
    ),
    Option(
        "csum_type", str, "crc32c",
        desc="checksum algorithm for block verification "
             "(bluestore_csum_type analog)",
        enum_values=("crc32c", "crc32c_16", "crc32c_8",
                     "xxhash32", "xxhash64", "none"),
    ),
    Option(
        "csum_block_size", int, 4096,
        desc="bytes per checksum block", min=16,
    ),
    Option(
        "ec_use_pallas", bool, True,
        desc="use the fused Pallas MXU encode kernel on TPU when the "
             "shape tiles (falls back to the einsum path otherwise)",
    ),
    Option(
        "ec_clay_kernels", bool, True,
        desc="serve traced CLAY fractional repair with the plane-"
             "blocked Pallas kernels (general d, any sub_chunk_no); "
             "off or unsupported geometries fall back to the XLA "
             "whole-tensor / itemized repair paths",
    ),
    Option(
        "ec_fused_csum", bool, True,
        desc="fuse per-csum-block CRC32C into the Pallas encode "
             "kernel (one HBM pass emits parity AND the k+m shards' "
             "block csums); consumers seed HashInfo and store blob "
             "csums from the kernel output instead of re-hashing on "
             "the host",
    ),
    Option(
        "ec_fused_csum_interpret", bool, False,
        desc="run the fused encode+csum kernel in Pallas interpreter "
             "mode off-TPU (tests/CI; production CPU paths keep the "
             "host csum fallback)",
        level="dev",
    ),
    Option(
        "csum_device_min_bytes", int, 1 << 18,
        desc="checksum batches below this many bytes run on the "
             "host scalar path (native C when loaded) instead of the "
             "device fold — per-dispatch latency dwarfs the hash "
             "there; 0 = always use the device",
        min=0,
    ),
    Option(
        "ec_recovery_verify", bool, True,
        desc="verify fully reconstructed shards against the persisted "
             "HashInfo cumulative crcs (device-batched) before "
             "pushing them to the replacement store",
    ),
    Option(
        "ec_use_sched", bool, True,
        desc="route sparse packet bit-matrix applies (liberation "
             "family encode/delta) through the schedule-native XOR "
             "kernel instead of the generic MXU engine — the "
             "jerasure_schedule_encode analog (ops/xor_schedule.py)",
    ),
    Option(
        "ec_sched_opt", bool, True,
        desc="superoptimize XOR schedules (arxiv 2108.02692): greedy "
             "pairwise CSE factors shared subexpressions across "
             "parity rows into VMEM-scratch intermediates, the DAG "
             "is reordered for operand locality, and the route gate "
             "moves to post-CSE op count (letting CSE-compressible "
             "inverted decode / LRC local-repair matrices ride the "
             "schedule route); off = the un-optimized selection-form "
             "schedule under the raw density gate, the pinned "
             "bit-equal escape hatch",
        see_also=("ec_use_sched",),
    ),
    Option(
        "ec_use_mesh", bool, True,
        desc="when a dispatch mesh is installed "
             "(parallel.dispatch.set_mesh), shard codec encode/decode/"
             "delta over it (stripes on dp, shards on sp with zero-pad, "
             "ring-XOR combine); falls back per-dispatch when neither "
             "the stripe batch nor the lane axis divides dp",
    ),
    Option(
        "objecter_inflight_per_osd", int, 64,
        desc="async objecter per-OSD session window: ops in flight to "
             "one OSD before further submissions park behind it "
             "(objecter_inflight_ops sliced per session)",
        min=1,
    ),
    Option(
        "osd_op_coalescing", bool, True,
        desc="coalesce concurrent client EC write ops queued at an "
             "OSD into one per-tick batch: the bookkeeping prelude "
             "runs serially under the op lock, per-PG groups execute "
             "concurrently, encodes share batched device dispatches "
             "(pipeline/dispatcher.py ring), and sub-writes pack one "
             "framed message per peer OSD",
    ),
    Option(
        "osd_coalesce_max", int, 16,
        desc="max client write ops coalesced into one tick batch",
        min=2,
    ),
    Option(
        "lockdep", bool, False,
        desc="arm the runtime lock-order / blocking-under-lock "
             "detector (utils/lockdep.py, the common/lockdep.cc + "
             "mutex_debug analog): DebugLock/DebugRLock construct "
             "tracked wrappers that record every acquire into a "
             "process-global lock-dependency graph, report order-"
             "inversion cycles and rank violations (cluster-log ERR "
             "+ `lockdep` perf counters + the admin-socket `lockdep` "
             "dump) WITHOUT deadlocking, and blocking_region() "
             "checkpoints flag blocking calls made while an op-"
             "serializing lock is held. Off (the default) the "
             "constructors hand back plain threading primitives — "
             "the flag is read once per lock construction, so arming "
             "applies to daemons built after the flip",
        level="dev",
    ),
    Option(
        "osd_peer_rpc_timeout", float, 10.0,
        desc="seconds an OSD waits for a peer sub-op reply before the "
             "RPC expires (reads error and re-plan; writes park; the "
             "peer is down-marked). Chaos/loadgen runs lower this so "
             "frames lost to the injected fault plane resolve within "
             "the client's resend ladder instead of stalling ticks",
        min=0.05,
    ),
    Option(
        "osd_subop_resend_interval", float, 0.0,
        desc="seconds before an un-replied peer sub-op (read or "
             "write) is retransmitted on a doubling ladder, 0 = never "
             "(the default: TCP links are lossless so a missing ack "
             "means a dead peer, and parked-write semantics stand). "
             "Lossy-link/chaos runs arm it so frames eaten by the "
             "injected fault plane resolve in fractions of the RPC "
             "deadline; retransmits are safe — sub-writes are "
             "idempotent (absolute extents + attrs), interval-fenced, "
             "and duplicate acks are absorbed exactly-once",
        min=0.0,
    ),
    Option(
        "msgr_fault_plane", bool, True,
        desc="consult the seeded network-fault plane "
             "(msg.messenger.net_faults) on framed links; off is the "
             "operator escape hatch that makes every armed rule inert "
             "without clearing it",
    ),
    Option(
        "msgr_native_codec", bool, True,
        desc="assemble/verify clear-mode wire frames through the "
             "native C codec (one call per frame: header + segment "
             "table + per-segment crc32c) when the native tier loads, "
             "and on a clear, uncompressed link over a kernel socket "
             "let that call do the socket I/O too (framed and written, "
             "or read and verified, without coming back to the "
             "interpreter in between); off (or CEPH_TPU_NO_NATIVE) "
             "routes every frame through the bit-identical pure-Python "
             "codec and Python socket I/O — the codec A/B's baseline arm",
    ),
    Option(
        "msgr_transport", str, "tcp",
        desc="transport lane for co-located peers: 'shm_ring' "
             "upgrades connections whose peer address resolves "
             "in-process to a shared-memory byte-ring pair "
             "(msg/shm_ring.py) at connect time, skipping the kernel "
             "socket round-trip; 'tcp' (default) keeps every link on "
             "loopback TCP. Remote/unresolved addresses always fall "
             "back to tcp; the net-fault plane injects above the "
             "transport either way",
        enum_values=("tcp", "shm_ring"),
    ),
    Option(
        "osd_op_num_shards", int, 1,
        desc="op-execution worker shards per OSD (osd_op_num_shards "
             "analog): ops route to a shard by (pool, pg) hash, each "
             "shard runs its own worker thread and op lock, so one "
             "EC write parked in drain_until no longer wedges every "
             "other PG's queue head (the flood-kill p99 cliff). 1 = "
             "the legacy single-worker path, bit-compatible with "
             "every existing test",
        min=1,
        max=64,
    ),
    Option(
        "osd_max_backfills", int, 1,
        desc="max concurrent backfills an OSD participates in, as "
             "primary (local reservation) or target (remote "
             "reservation) — the backfill_reservation.rst throttle",
        min=1,
    ),
    Option(
        "osd_scrub_min_interval", float, 86400.0,
        desc="seconds between scheduled (shallow) scrubs of a PG",
        min=0.0,
    ),
    Option(
        "osd_deep_scrub_interval", float, 604800.0,
        desc="seconds between scheduled deep scrubs of a PG "
             "(osd_deep_scrub_interval)",
        min=0.0,
    ),
    Option(
        "osd_scrub_interval_randomize_ratio", float, 0.5,
        desc="spread scheduled scrubs by up to this fraction of the "
             "interval (avoids synchronized scrub storms)",
        min=0.0,
    ),
    Option(
        "osd_deep_scrub_randomize_ratio", float, 0.15,
        desc="chance a scheduled shallow scrub deepens early "
             "(spreads deep-scrub load inside the deep interval)",
        min=0.0,
    ),
    Option(
        "osd_scrub_auto_repair", bool, False,
        desc="scheduled deep scrubs repair inconsistencies they find "
             "(osd_scrub_auto_repair)",
    ),
    Option(
        "osd_max_scrubs", int, 1,
        desc="concurrent scheduled scrubs per daemon (reservation "
             "cap, osd_max_scrubs)",
        min=1,
    ),
    Option(
        "osd_deep_scrub_stride", int, 524288,
        desc="bytes read per shard per step during deep scrub "
             "(bounds scrub memory/latency; osd_deep_scrub_stride)",
        min=4096,
    ),
    Option(
        "ec_recovery_retries", int, 3,
        desc="re-plan attempts when survivors error mid-recovery",
        min=0,
    ),
    Option(
        "ec_trace_history", int, 512,
        desc="recent spans kept for dump_historic_ops", min=0,
    ),
    Option(
        "osd_enable_op_tracker", bool, True,
        desc="register every live operation (client op, RMW op, peer "
             "sub-op, peering pass, recovery push, backfill item) "
             "with the process OpTracker: dump_ops_in_flight shows "
             "age-sorted live ops with their event timelines and the "
             "slow-op watchdog complains past osd_op_complaint_time. "
             "Off = register() hands back a null op whose marks are "
             "one attribute access (the bench A/B's untracked arm)",
    ),
    Option(
        "osd_op_complaint_time", float, 30.0,
        desc="seconds a tracked op may stay live before the watchdog "
             "flags it slow: bumps the owning daemon's slow_ops "
             "counters (gauge + age histogram on perf dump and the "
             "exporter) and posts a WRN complaint to the cluster log "
             "(osd_op_complaint_time analog)",
        min=0.01,
    ),
    Option(
        "cluster_log_file", str, "",
        desc="JSONL sink for cluster-log events (the ceph.log file "
             "role); empty = in-memory ring only. Applied when the "
             "first event lands after the value changes",
    ),
    Option(
        "ec_host_dispatch_bytes", int, 1 << 20,
        desc="ops at or below this many input bytes encode/decode on "
             "the host GF tables instead of the device (per-dispatch "
             "latency dominates tiny ops; 0 = always use the device)",
        min=0,
    ),
    Option(
        "osd_stats_report_interval", float, 0.5,
        desc="seconds between a primary's PG-stats reports to the "
             "monitor (the MPGStats cadence feeding the PGMap "
             "aggregate: state bits, object/byte counts, degraded/"
             "misplaced tallies, cumulative client+recovery IO). "
             "0 disables reporting — the bench cluster phase's "
             "stats-off A/B arm",
        min=0.0,
    ),
    Option(
        "osd_device_capacity_bytes", int, 1 << 30,
        desc="notional device capacity an OSD reports in its osd_stat "
             "when its store has no real device size (MemStore); "
             "feeds `df` totals and the OSD_NEARFULL check",
        min=1,
    ),
    Option(
        "mon_pg_stuck_threshold", float, 30.0,
        desc="seconds a PG may stay non-clean before the mgr health "
             "model raises PG_STUCK (mon_pg_stuck_threshold analog; "
             "ages derive from the stats plane's last-clean stamps)",
        min=0.0,
    ),
    Option(
        "mon_osd_nearfull_ratio", float, 0.85,
        desc="osd_stat fill fraction at which the mgr health model "
             "raises OSD_NEARFULL (mon_osd_nearfull_ratio analog)",
        min=0.0, max=1.0,
    ),
    Option(
        "mon_osd_min_down_reporters", int, 2,
        desc="distinct peer reports required before the monitor marks "
             "an OSD down (mon_osd_min_down_reporters analog)", min=1,
    ),
    Option(
        "mon_osd_down_out_interval", float, 600.0,
        desc="seconds an OSD may stay down before the monitor marks "
             "it out and data rebalances", min=0,
    ),
    Option(
        "osd_op_qos", bool, True,
        desc="schedule client ops under per-tenant dmClock classes "
             "(client.<tenant> / client.<pool>) with byte-"
             "proportional cost and pool-metadata QoS specs; off is "
             "the escape hatch that collapses every client op back "
             "into the single undifferentiated 'client' class — the "
             "noisy-neighbor A/B's unprotected arm",
    ),
    Option(
        "osd_mclock_profile", str, "balanced",
        desc="recovery-vs-client slosh knob (osd_mclock_profile "
             "analog): which fraction of osd_mclock_capacity each "
             "base class is guaranteed. high_client starves "
             "background work for client latency; high_recovery "
             "floors client share to drain degraded PGs fast; "
             "reservations clients measurably don't use re-grant to "
             "recovery/backfill each tick (cluster/qos.py "
             "derive_profiles)",
        enum_values=("high_client", "balanced", "high_recovery"),
        level="basic",
    ),
    Option(
        "osd_mclock_capacity", float, 1000.0,
        desc="notional OSD service rate in mClock cost units/s "
             "(1 unit = one op quantum or 64 KiB of payload) that "
             "profile fractions and QoS reservations divide "
             "(osd_mclock_max_capacity_iops analog)",
        min=1.0,
    ),
    Option(
        "debug_nan_check", bool, False,
        desc="enable jax_debug_nans: every kernel output is checked "
             "for NaNs (the WITH_UBSAN-style opt-in debug mode; EC "
             "math is integer so any NaN is a framework bug)",
        level="dev",
    ),
    Option(
        "debug_disable_jit", bool, False,
        desc="run traced code eagerly (jax_disable_jit) so Python "
             "debuggers can step through kernel math", level="dev",
    ),
)


class ConfigProxy:
    """Effective-value resolver + observer hub (config_proxy.h)."""

    def __init__(self, options: tuple[Option, ...] = OPTIONS) -> None:
        self._lock = threading.Lock()
        self.schema: dict[str, Option] = {o.name: o for o in options}
        self._layers: dict[str, dict[str, object]] = {
            layer: {} for layer in LAYERS
        }
        self._layers["default"] = {
            o.name: o.default for o in options
        }
        self._observers: list[tuple[str, object]] = []
        self._load_env()

    # -- layers --------------------------------------------------------
    def _load_env(self) -> None:
        for opt in self.schema.values():
            raw = os.environ.get(ENV_PREFIX + opt.name.upper())
            if raw is not None:
                self._layers["env"][opt.name] = opt.parse(raw)

    def load_file(self, path: str) -> None:
        """Load a JSON config file into the file layer."""
        with open(path) as f:
            data = json.load(f)
        for name, raw in data.items():
            opt = self._require(name)
            self.set(name, raw, layer="file")

    def _require(self, name: str) -> Option:
        opt = self.schema.get(name)
        if opt is None:
            raise KeyError(f"unknown option {name!r}")
        return opt

    # -- access --------------------------------------------------------
    def get(self, name: str):
        opt = self._require(name)
        with self._lock:
            for layer in reversed(LAYERS):
                if name in self._layers[layer]:
                    return self._layers[layer][name]
        return opt.default

    def get_source(self, name: str) -> str:
        self._require(name)
        with self._lock:
            for layer in reversed(LAYERS):
                if name in self._layers[layer]:
                    return layer
        return "default"

    def set(self, name: str, raw, layer: str = "runtime") -> None:
        opt = self._require(name)
        if layer not in LAYERS or layer == "default":
            raise ValueError(f"cannot set layer {layer!r}")
        val = opt.parse(raw)
        before = self.get(name)
        with self._lock:
            self._layers[layer][name] = val
        after = self.get(name)
        if after != before:
            self._notify(name, after)

    @contextmanager
    def override(self, **kv):
        """Temporarily set options in the runtime layer — the test
        form of ``config set``; restores prior state (including
        absence) on exit, firing observers both ways."""
        saved = {}
        with self._lock:
            for name in kv:
                layer = self._layers["runtime"]
                saved[name] = (name in layer, layer.get(name))
        try:
            # inside the try: a failing set mid-application must not
            # leak the overrides already applied
            for name, raw in kv.items():
                self.set(name, raw, layer="runtime")
            yield
        finally:
            for name, (present, old) in saved.items():
                if present:
                    self.set(name, old, layer="runtime")
                else:
                    self.rm(name, layer="runtime")

    def rm(self, name: str, layer: str = "runtime") -> None:
        self._require(name)
        before = self.get(name)
        with self._lock:
            self._layers[layer].pop(name, None)
        after = self.get(name)
        if after != before:
            self._notify(name, after)

    def show(self) -> dict:
        """Effective value + winning layer per option (config show)."""
        return {
            name: {"value": self.get(name), "source": self.get_source(name)}
            for name in sorted(self.schema)
        }

    # -- observers (config_obs.h) --------------------------------------
    def add_observer(self, prefix: str, fn) -> None:
        """``fn(name, new_value)`` fires when a matching key changes."""
        self._observers.append((prefix, fn))

    def _notify(self, name: str, value) -> None:
        for prefix, fn in self._observers:
            if name.startswith(prefix):
                fn(name, value)


# Process-global config, mirroring g_conf().
config = ConfigProxy()
