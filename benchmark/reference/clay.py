"""The Clay (coupled-layer) MSR code of a ``plugin=clay`` pool, written
straight from its description (Vajha et al., "Clay Codes: Moulding MDS
Codes to Yield an MSR Code", FAST 2018; upstream
``doc/rados/operations/erasure-code-clay.rst``) in numpy over this
directory's GF(2^8): no kernel, no batching beyond whole-array numpy
over stripes and bytes, nothing of the program.

The construction, for a pool ``k, m, d`` (``pool["d"]``, default
k+m-1):

- q = d-k+1; nu pads k+m up to a multiple of q with virtual chunks that
  hold zeros; t = (k+m+nu)/q; a chunk is ``q**t`` sub-chunks ("planes")
  of equal length, in order.
- The k+m+nu nodes stand on a q x t grid: node n is at x = n % q,
  y = n // q. Chunk i is node i for i < k (data), the virtual nodes
  follow, parity chunk k+j is node k+nu+j.
- Plane z has t base-q digits z_0 .. z_{t-1}, z_0 the most significant.
  Node (x, y) is a *dot* of plane z where z_y == x. Otherwise it is
  *paired* with node (z_y, y) in the plane z' that is z with digit y
  set to x.
- What is stored is the coupled value C. The uncoupled value U of a dot
  is its C. For a pair, with "hi" the member whose x is larger,

      (U_hi, U_lo) = PAIR x (C_hi, C_lo),   PAIR = [[3, 2], [2, 3]]

  over GF(2^8): invertible (its determinant is 3*3 + 2*2 = 1) and its
  own inverse.
- In every plane the U of all nodes is a codeword of the scalar MDS
  code: the systematic Vandermonde code of ``rs_vandermonde`` with
  k+nu data symbols and m parities, node order as above.

Any m nodes can be rebuilt (``_decode``): take the planes by ascending
*intersection score* (how many erased nodes are dots of the plane);
within a score, U of every known node is at hand (a pair's erased
partner was rebuilt one score earlier), the MDS code gives U of the
erased nodes, and their C follows from the pair equation. Encoding is
rebuilding the m parity nodes from the data.

What this file has to share with the program for the stored shards to
be equal, stated here as the code's definition, each with where the
program states the same:

- PAIR: ``codecs/clay.py`` takes rows 2 and 3 of
  ``vandermonde_rs_matrix(2, 2)`` (``ClayCodec._g4``: C_hi, C_lo, U_hi,
  U_lo as functions of (C_hi, C_lo)), which are (3, 2) and (2, 3);
- the node order and the place of the virtual nodes:
  ``ClayCodec._to_node``;
- digit order (z_0 most significant): ``ClayCodec._plane_vector``;
- which member is "hi" (the larger x): ``ClayCodec._pair_idx``;
- the scalar code: the pool's ``technique`` (``reed_sol_van``) with
  k+nu and m, ``ClayCodec.init``'s ``mds_profile``, which for this
  repo is ``rs_vandermonde.coding_matrix`` (PERF.md section 7).

``tests/test_clay_reference.py`` holds each of them against the
program."""

from __future__ import annotations

import numpy as np

from . import gf256, rs_vandermonde

PAIR = ((3, 2), (2, 3))


class Geometry:
    def __init__(self, k: int, m: int, d: int | None) -> None:
        d = k + m - 1 if d is None else int(d)
        if not k + 1 <= d <= k + m - 1:
            raise ValueError(f"d={d} outside [{k + 1}, {k + m - 1}]")
        self.k, self.m, self.d = k, m, d
        self.q = d - k + 1
        self.nu = -(k + m) % self.q
        self.t = (k + m + self.nu) // self.q
        self.nodes = self.q * self.t
        self.planes = self.q ** self.t

    def node_of(self, chunk: int) -> int:
        return chunk if chunk < self.k else chunk + self.nu

    def digits(self, z: int) -> list[int]:
        out = [0] * self.t
        for y in range(self.t - 1, -1, -1):
            out[y] = z % self.q
            z //= self.q
        return out

    def with_digit(self, z: int, y: int, x: int) -> int:
        """Plane z with digit y set to x."""
        weight = self.q ** (self.t - 1 - y)
        return z + (x - self.digits(z)[y]) * weight

    def repair_planes(self, node: int) -> list[int]:
        """The planes of which ``node`` is a dot, ascending."""
        x, y = node % self.q, node // self.q
        return [z for z in range(self.planes) if self.digits(z)[y] == x]


def _geometry(k: int, m: int, pool: dict | None) -> Geometry:
    return Geometry(k, m, (pool or {}).get("d"))


def _gf_scale(g: int, a: np.ndarray) -> np.ndarray:
    return gf256.mul_table(g)[a]


def _pair_forward(c_hi, c_lo):
    """(U_hi, U_lo) from (C_hi, C_lo)."""
    return (
        _gf_scale(PAIR[0][0], c_hi) ^ _gf_scale(PAIR[0][1], c_lo),
        _gf_scale(PAIR[1][0], c_hi) ^ _gf_scale(PAIR[1][1], c_lo),
    )


def _solve_c(known_c, known_is_hi: bool, u_other):
    """One member's C from its partner's C and its own U: the pair
    equation of that member, solved for its own C."""
    if known_is_hi:
        # U_lo = P10*C_hi + P11*C_lo
        own, partner = PAIR[1][1], PAIR[1][0]
    else:
        # U_hi = P00*C_hi + P01*C_lo
        own, partner = PAIR[0][0], PAIR[0][1]
    return _gf_scale(
        gf256.inv(own), u_other ^ _gf_scale(partner, known_c)
    )


def _solve_partner(own_c, own_is_hi: bool, own_u):
    """The partner's C from one member's own C and U: that member's
    pair equation, solved for the other C."""
    if own_is_hi:
        own, partner = PAIR[0][0], PAIR[0][1]
    else:
        own, partner = PAIR[1][1], PAIR[1][0]
    return _gf_scale(gf256.inv(partner), own_u ^ _gf_scale(own, own_c))


def _decode(geo: Geometry, C: dict[int, np.ndarray], erased: set[int]) -> None:
    """Fill ``C[node]`` (arrays ``[stripes, planes, sub]``) of the
    ``erased`` nodes, at most m of them, in place."""
    if len(erased) > geo.m:
        raise ValueError(f"{len(erased)} erasures, the code bears {geo.m}")
    q = geo.q
    shape = next(iter(C.values())).shape
    U = {n: np.zeros(shape, np.uint8) for n in range(geo.nodes)}
    known = [n for n in range(geo.nodes) if n not in erased]
    # the scalar code: U of the erased nodes from the first k+nu known
    # ones, through the systematic generator's surviving rows
    ks = geo.k + geo.nu
    gen = np.concatenate(
        [np.eye(ks, dtype=np.uint8), rs_vandermonde.coding_matrix(ks, geo.m)]
    )
    basis = known[:ks]
    lost = sorted(erased)
    rebuild = None
    if lost:
        inverse = gf256.invert(gen[basis])
        rebuild = np.zeros((len(lost), ks), np.uint8)
        for r, node in enumerate(lost):
            for c in range(ks):
                acc = 0
                for j in range(ks):
                    acc ^= gf256.mul(int(gen[node, j]), int(inverse[j, c]))
                rebuild[r, c] = acc
    by_score: dict[int, list[int]] = {}
    for z in range(geo.planes):
        dig = geo.digits(z)
        score = sum(1 for n in erased if dig[n // q] == n % q)
        by_score.setdefault(score, []).append(z)
    for score in sorted(by_score):
        planes = by_score[score]
        for z in planes:
            dig = geo.digits(z)
            for n in known:
                x, y = n % q, n // q
                if dig[y] == x:
                    U[n][:, z] = C[n][:, z]
                    continue
                partner, zp = y * q + dig[y], geo.with_digit(z, y, x)
                # an erased partner's C in plane zp is rebuilt already:
                # there this node is the dot, so zp scores one lower
                if x > dig[y]:
                    U[n][:, z] = _pair_forward(C[n][:, z], C[partner][:, zp])[0]
                else:
                    U[n][:, z] = _pair_forward(C[partner][:, zp], C[n][:, z])[1]
        if not lost:
            continue
        rows = np.stack([
            U[n][:, planes].reshape(-1) for n in basis
        ])
        out = gf256.apply_matrix(rebuild, rows)
        for r, node in enumerate(lost):
            U[node][:, planes] = out[r].reshape(
                shape[0], len(planes), shape[2]
            )
        for z in planes:
            dig = geo.digits(z)
            for n in lost:
                x, y = n % q, n // q
                if dig[y] == x:
                    C[n][:, z] = U[n][:, z]
                    continue
                partner, zp = y * q + dig[y], geo.with_digit(z, y, x)
                if partner not in erased:
                    C[n][:, z] = _solve_c(
                        C[partner][:, zp], dig[y] > x, U[n][:, z]
                    )
                elif x > dig[y]:
                    # both erased, both U known (zp has this score
                    # too): PAIR is its own inverse
                    C[n][:, z], C[partner][:, zp] = _pair_forward(
                        U[n][:, z], U[partner][:, zp]
                    )


def _nodes_from(geo: Geometry, chunks: dict[int, np.ndarray], sub: int):
    """Chunk arrays ``[shard_bytes]`` -> ``C`` by node, missing and
    virtual ones zero, and the set of erased nodes."""
    width = geo.planes * sub
    some = next(iter(chunks.values()))
    stripes = some.size // width
    C, erased = {}, set()
    for chunk in range(geo.k + geo.m):
        node = geo.node_of(chunk)
        if chunk in chunks:
            C[node] = np.array(chunks[chunk], np.uint8).reshape(
                stripes, geo.planes, sub
            )
        else:
            C[node] = np.zeros((stripes, geo.planes, sub), np.uint8)
            erased.add(node)
    for node in range(geo.k, geo.k + geo.nu):
        C[node] = np.zeros((stripes, geo.planes, sub), np.uint8)
    return C, erased


def shards_of(
    obj: bytes, k: int, m: int, chunk_size: int, pool: dict | None = None
) -> np.ndarray:
    """The k+m shards of ``obj`` as ``[k+m, shard_bytes]`` uint8: the
    object zero-padded to whole stripes of k chunks, shard s holding
    chunk s of every stripe, every stripe a Clay codeword."""
    geo = _geometry(k, m, pool)
    if chunk_size % geo.planes:
        raise ValueError(
            f"chunk_size {chunk_size} is not {geo.planes} sub-chunks"
        )
    stripe = k * chunk_size
    n_stripes = -(-len(obj) // stripe)
    buf = np.zeros(n_stripes * stripe, np.uint8)
    buf[: len(obj)] = np.frombuffer(obj, np.uint8)
    data = (
        buf.reshape(n_stripes, k, chunk_size)
        .transpose(1, 0, 2)
        .reshape(k, n_stripes * chunk_size)
    )
    C, erased = _nodes_from(
        geo, {i: data[i] for i in range(k)}, chunk_size // geo.planes
    )
    _decode(geo, C, erased)
    parity = np.stack([
        C[geo.node_of(k + j)].reshape(-1) for j in range(m)
    ])
    return np.concatenate([data, parity], axis=0)


def decode_data(
    shards: dict[int, np.ndarray], k: int, m: int, pool: dict | None = None
) -> np.ndarray:
    """The k data shards from any k of the k+m stored ones (more are
    taken as they come: every one given is used as known)."""
    geo = _geometry(k, m, pool)
    if len(shards) < k:
        raise ValueError(f"need {k} shards, have {len(shards)}")
    chunk_size = (pool or {}).get("chunk_size")
    some = next(iter(shards.values()))
    if not chunk_size:
        chunk_size = some.size  # one stripe
    C, erased = _nodes_from(geo, shards, chunk_size // geo.planes)
    _decode(geo, C, erased)
    return np.stack([C[i].reshape(-1) for i in range(k)])


def object_from_data_shards(
    data: np.ndarray, size: int, chunk_size: int
) -> bytes:
    return rs_vandermonde.object_from_data_shards(data, size, chunk_size)


def repair(
    helpers: dict[int, np.ndarray], lost: int, k: int, m: int,
    pool: dict | None = None,
) -> np.ndarray:
    """Chunk ``lost`` from the repair planes alone of every other chunk
    (d = k+m-1): ``helpers[c]`` is ``[stripes, planes/q, sub]``, chunk
    c's sub-chunks at ``Geometry.repair_planes(node of lost)``, in
    order. The MSR property, used by the tests to say which planes a
    helper has to send; a pool with d < k+m-1 is not covered here.

    In a repair plane the lost node is a dot, so its whole row y is
    erased for the scalar code and every other row is known: U of a
    helper outside the row pairs inside the repair planes. The row's U
    then gives the lost C in the plane itself (the dot) and, through
    each row member's pair equation, in the q-1 planes that differ in
    digit y."""
    geo = _geometry(k, m, pool)
    if geo.d != k + m - 1:
        raise ValueError("this repair is written for d = k+m-1")
    q = geo.q
    node_l = geo.node_of(lost)
    x_l, y_l = node_l % q, node_l // q
    planes = geo.repair_planes(node_l)
    at = {z: i for i, z in enumerate(planes)}
    some = next(iter(helpers.values()))
    stripes, _, sub = some.shape
    H = {geo.node_of(c): np.asarray(v, np.uint8) for c, v in helpers.items()}
    for node in range(geo.k, geo.k + geo.nu):
        H[node] = np.zeros_like(some)
    row = [y_l * q + x for x in range(q)]
    known = [n for n in range(geo.nodes) if n not in row]
    U = {n: np.zeros_like(some) for n in range(geo.nodes)}
    for z in planes:
        dig = geo.digits(z)
        for n in known:
            x, y = n % q, n // q
            if dig[y] == x:
                U[n][:, at[z]] = H[n][:, at[z]]
                continue
            partner, zp = y * q + dig[y], geo.with_digit(z, y, x)
            if x > dig[y]:
                U[n][:, at[z]] = _pair_forward(
                    H[n][:, at[z]], H[partner][:, at[zp]]
                )[0]
            else:
                U[n][:, at[z]] = _pair_forward(
                    H[partner][:, at[zp]], H[n][:, at[z]]
                )[1]
    ks = geo.k + geo.nu
    gen = np.concatenate(
        [np.eye(ks, dtype=np.uint8), rs_vandermonde.coding_matrix(ks, geo.m)]
    )
    basis = known[:ks]
    inverse = gf256.invert(gen[basis])
    data_u = gf256.apply_matrix(
        inverse, np.stack([U[n].reshape(-1) for n in basis])
    )
    row_u = gf256.apply_matrix(gen[row], data_u)
    for r, n in enumerate(row):
        U[n] = row_u[r].reshape(some.shape)
    out = np.zeros((stripes, geo.planes, sub), np.uint8)
    for z in planes:
        out[:, z] = U[node_l][:, at[z]]
        for n in row:
            x = n % q
            if n == node_l:
                continue
            # member (x, y_l) of plane z pairs with the lost node in
            # the plane whose digit y_l is x
            out[:, geo.with_digit(z, y_l, x)] = _solve_partner(
                H[n][:, at[z]], x > x_l, U[n][:, at[z]]
            )
    return out.reshape(stripes, geo.planes * sub)
