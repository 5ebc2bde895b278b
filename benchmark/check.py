"""How ``correct`` is decided: the guarantees the configuration states,
held against the plain reference, on what the timed path produced.

Once the window has closed, a sample of the objects that the window
wrote or read is drawn from the seed. For each:

- the client reads it back and the bytes equal the seed's;
- the k+m shards in the OSDs' stores equal ``reference/``'s encode of
  those bytes, byte for byte (a healthy read never touches parity, so
  only this catches a wrong parity from the device);
- every store's cumulative crc32c of every shard equals the
  reference's wherever it covers the whole shard, and it has to cover
  it where the object is as first written (the program folds the
  kernel's per-4-KiB words into it; an overwrite may clear it or leave
  it short by design, and scrub then passes over it);
- the reference rebuilds the object from a seeded choice of k stored
  shards with a parity shard among them (any k of k+m suffice).

Every comparison is exact: its limit is 0 mismatches."""

from __future__ import annotations

import json

import numpy as np

from .reference import crc32c, rs_vandermonde
from .traffic import generator as traffic

HINFO_ATTR = "hinfo_key"
CRC_SEED = 0xFFFFFFFF


def sample_objects(gen, seed: int, count: int) -> list[int]:
    """Up to ``count`` objects that ops issued in the window touched
    and whose bytes are known (no failed write, nothing in flight),
    drawn from the seed."""
    issued, _ = gen.window_samples()
    touched = sorted({
        s.idx for s in issued
        if s.ok and gen.objects[s.idx].exists and not gen.objects[s.idx].busy
    })
    rng = np.random.default_rng(traffic._seed_words(seed) + [0xC4EC])
    if len(touched) > count:
        touched = sorted(
            rng.choice(touched, size=count, replace=False).tolist()
        )
    return touched


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
    and how many of each were compared."""
    pool = config["pool"]
    k, m, chunk = pool["k"], pool["m"], pool["chunk_size"]
    out = {
        "objects": 0, "shards": 0, "csum_objects": 0,
        "read_mismatch": 0, "shard_mismatch": 0, "shard_missing": 0,
        "csum_mismatch": 0, "decode_mismatch": 0,
    }
    picked = sample_objects(gen, seed, count)
    keys = {osd: _shard_keys(store) for osd, store in cluster.stores.items()}
    rng = np.random.default_rng(traffic._seed_words(seed) + [0xDEC0])
    hashed_objects: list[tuple[np.ndarray, list[dict]]] = []
    for idx in picked:
        st = gen.objects[idx]
        oid = gen.oid(idx)
        image = traffic.expected_image(
            gen.seed, idx, st.version, st.n_patches, gen.object_size,
            gen.max_patch,
        )
        out["objects"] += 1
        if bytes(cluster.io.read(oid)) != image:
            out["read_mismatch"] += 1
        want = rs_vandermonde.shards_of(image, k, m, chunk)
        stored: dict[int, np.ndarray] = {}
        fresh = st.version == 1 and st.n_patches == 0
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
            rebuilt = rs_vandermonde.decode_data(
                {int(s): stored[int(s)] for s in use}, k, m
            )
            if rs_vandermonde.object_from_data_shards(
                rebuilt, len(image), chunk
            ) != image:
                out["decode_mismatch"] += 1
        else:
            out["decode_mismatch"] += 1
    if hashed_objects:
        # one byte-serial pass over every reference shard of every
        # object that has them, at once
        rows = np.concatenate([want for want, _ in hashed_objects], axis=0)
        hashes = crc32c.crc32c_rows(CRC_SEED, rows).reshape(-1, k + m)
        for (want, hinfos), row in zip(hashed_objects, hashes):
            expect = [int(v) for v in row]
            for hinfo in hinfos:
                if hinfo["total_chunk_size"] != want.shape[1] or [
                    int(v) for v in hinfo["hashes"]
                ] != expect:
                    out["csum_mismatch"] += 1
    return out


LIMITS = {
    "read_mismatch": 0, "shard_mismatch": 0, "shard_missing": 0,
    "csum_mismatch": 0, "decode_mismatch": 0,
}
