"""How ``correct`` is decided: the guarantees the configuration states,
held against the plain reference, on what the timed path produced.

Once the window has closed, a sample of the objects that the window
wrote or read is drawn from the seed. For each:

- the client reads it back and the bytes equal the seed's;
- the k+m shards in the OSDs' stores equal the encode, by the pool's
  plain reference, of those bytes at the object's length now, byte for
  byte (a healthy read never touches parity, so only this catches a
  wrong parity from the device);
- every store's cumulative crc32c of every shard equals the
  reference's wherever it covers the whole shard, and it has to cover
  it where the object was created and since then only appended to (the
  program folds the kernel's per-4-KiB words into it and carries it
  from one append to the next; an overwrite may clear it or leave it
  short by design, and scrub then passes over it);
- the reference rebuilds the object from a seeded choice of k stored
  shards with a parity shard among them (any k of k+m suffice).

And for a sample, drawn from the seed, of the names that the window
deleted and that are absent now: the client's ``stat`` and ``read``
answer ENOENT, and no OSD's store holds a key of any of its shards.

The reference is the configuration's own (``files.reference``): this
file imports no pool's code by name. Every comparison is exact: its limit is 0
mismatches."""

from __future__ import annotations

import json

import numpy as np

from . import files
from .reference import crc32c
from .traffic import generator as traffic

HINFO_ATTR = "hinfo_key"
CRC_SEED = 0xFFFFFFFF


def _draw(items: list[int], seed: int, salt: int, count: int) -> list[int]:
    """At most ``count`` of ``items``, drawn from the seed."""
    if len(items) <= count:
        return items
    rng = np.random.default_rng(traffic._seed_words(seed) + [salt])
    return sorted(rng.choice(items, size=count, replace=False).tolist())


def sample_objects(gen, seed: int, count: int) -> list[int]:
    """Up to ``count`` objects that ops issued in the window touched
    and whose bytes are known (no failed write, nothing in flight),
    drawn from the seed."""
    issued, _ = gen.window_samples()
    touched = sorted({
        s.idx for s in issued
        if s.ok and gen.objects[s.idx].exists and not gen.objects[s.idx].busy
    })
    return _draw(touched, seed, 0xC4EC, count)


def sample_deleted(gen, seed: int, count: int) -> list[int]:
    """Up to ``count`` names that a delete issued in the window removed
    and that nothing has made again, drawn from the seed."""
    issued, _ = gen.window_samples()
    gone = sorted({
        s.idx for s in issued
        if s.ok and s.kind == "delete" and not gen.objects[s.idx].exists
        and not gen.objects[s.idx].busy and not gen.objects[s.idx].retired
    })
    return _draw(gone, seed, 0xDE1E, count)


def _shard_keys(store) -> dict[tuple[str, int], str]:
    """(oid, shard) -> the store's key (``<pool id>:<oid>#s<n>``)."""
    keys = {}
    for key in store.list_objects():
        name, sep, shard = key.rpartition("#s")
        if sep:
            keys[(name.partition(":")[2], int(shard))] = key
    return keys


def check(cluster, gen, config: dict, seed: int, count: int) -> dict:
    """The numbers compared, each a count of mismatches with limit 0,
    how many of each were compared, and ``client_ops``, the ops this
    comparison sent through the client (the objecter's ledger counts
    them)."""
    pool = config["pool"]
    k, m, chunk = pool["k"], pool["m"], pool["chunk_size"]
    reference = files.reference(config)
    out = {
        "objects": 0, "shards": 0, "csum_objects": 0, "deleted_objects": 0,
        "read_mismatch": 0, "shard_mismatch": 0, "shard_missing": 0,
        "csum_mismatch": 0, "decode_mismatch": 0, "delete_visible": 0,
        "shard_leftover": 0, "client_ops": 0,
    }
    picked = sample_objects(gen, seed, count)
    keys = {osd: _shard_keys(store) for osd, store in cluster.stores.items()}
    rng = np.random.default_rng(traffic._seed_words(seed) + [0xDEC0])
    hashed_objects: list[tuple[np.ndarray, list[dict]]] = []
    for idx in picked:
        st = gen.objects[idx]
        oid = gen.oid(idx)
        image = gen.image(idx)
        out["objects"] += 1
        out["client_ops"] += 1
        if bytes(cluster.io.read(oid)) != image:
            out["read_mismatch"] += 1
        want = reference.shards_of(image, k, m, chunk)
        stored: dict[int, np.ndarray] = {}
        fresh = gen.append_only(idx)
        hinfos: list[dict] = []
        acting = cluster.mon.osdmap.object_to_acting(cluster.pool, oid)
        for shard, osd in enumerate(acting):
            if osd < 0 or osd in cluster.dead:
                continue  # a hole: not part of what is served
            store = cluster.stores[osd]
            key = keys[osd].get((oid, shard))
            if key is None:
                out["shard_missing"] += 1
                continue
            got = np.frombuffer(store.read(key), np.uint8)
            out["shards"] += 1
            if got.shape != want[shard].shape or not np.array_equal(
                got, want[shard]
            ):
                out["shard_mismatch"] += 1
                continue
            stored[shard] = got
            hinfo = json.loads(store.getattr(key, HINFO_ATTR).decode())
            if fresh or hinfo["total_chunk_size"] == want.shape[1]:
                hinfos.append(hinfo)
        if hinfos:
            out["csum_objects"] += 1
            hashed_objects.append((want, hinfos))
        if len(stored) >= k:
            parity = [s for s in stored if s >= k]
            data = [s for s in stored if s < k]
            n_par = min(len(parity), m)
            use = list(rng.permutation(parity)[:n_par]) + list(
                rng.permutation(data)[: k - n_par]
            )
            rebuilt = reference.decode_data(
                {int(s): stored[int(s)] for s in use}, k, m
            )
            if reference.object_from_data_shards(
                rebuilt, len(image), chunk
            ) != image:
                out["decode_mismatch"] += 1
        else:
            out["decode_mismatch"] += 1
    # one byte-serial pass over every reference shard of every object
    # of one length that has them, at once
    by_width: dict[int, list] = {}
    for want, hinfos in hashed_objects:
        by_width.setdefault(want.shape[1], []).append((want, hinfos))
    for width, group in by_width.items():
        rows = np.concatenate([want for want, _ in group], axis=0)
        hashes = crc32c.crc32c_rows(CRC_SEED, rows).reshape(-1, k + m)
        for (_want, hinfos), row in zip(group, hashes):
            expect = [int(v) for v in row]
            for hinfo in hinfos:
                if hinfo["total_chunk_size"] != width or [
                    int(v) for v in hinfo["hashes"]
                ] != expect:
                    out["csum_mismatch"] += 1
    for idx in sample_deleted(gen, seed, count):
        oid = gen.oid(idx)
        out["deleted_objects"] += 1
        for ask in (cluster.io.stat, cluster.io.read):
            out["client_ops"] += 1
            try:
                ask(oid)
            except FileNotFoundError:
                continue
            out["delete_visible"] += 1
        out["shard_leftover"] += sum(
            (oid, shard) in keys[osd]
            for osd in keys for shard in range(k + m)
        )
    return out


LIMITS = {
    "read_mismatch": 0, "shard_mismatch": 0, "shard_missing": 0,
    "csum_mismatch": 0, "decode_mismatch": 0, "delete_visible": 0,
    "shard_leftover": 0,
}
