"""Seeded Criteo-shape data: rows, keys, and the files the program reads.

One generator for every cell. From `--seed` it draws, per file part, rows
of 39 fields (13 integer + 26 categorical). Which values a field takes,
and how often, is data: a file under `benchmark/keys/` (a configuration
names it) gives each field's number of distinct values and the exponent
of the bounded power law its values are drawn from. The label comes from
a planted linear model, so that a learner that works pushes logloss well
under ln 2. Rows are written as Criteo text or as `crb` record files, and
any batch can be given again as (bucket ids, labels) for the plain
reference — from the generator's own CityHash64 and its own arithmetic,
not from anything the program parsed.

Copied (PR 23) from `bench.py` (`mix_field_values`) and `chip_smoke.py`
(`write_criteo_files`, the planted labels); the originals are listed in
PERF.md's open questions for a later PR to retire. Nothing here imports
them.
"""

from __future__ import annotations

import json
import os

import numpy as np

NNZ = 39            # Criteo: 13 integer + 26 categorical fields
N_INT = 13
# margin = PLANT_BIAS + sum of 39 weights uniform in +-PLANT_SCALE/2
# (std ~1.8, click rate ~0.27)
PLANT_SCALE = 1.0
PLANT_BIAS = -1.5

_U = np.uint64
K0 = 0xC3A5C85C97CB3127
K2 = 0x9AE16A3B2F90404F
_M = (1 << 64) - 1


class KeyModel:
    """A file of benchmark/keys/: per field the number of distinct values
    and the exponent of the bounded power law they are drawn from."""

    def __init__(self, name: str):
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "keys", name + ".json")) as fh:
            spec = json.load(fh)
        self.name = name
        self.cards = [int(v) for v in spec["integer_cardinalities"]
                      + spec["categorical_cardinalities"]]
        self.skew = [float(v) for v in spec["integer_skew"]
                     + spec["categorical_skew"]]
        if len(self.cards) != NNZ or len(self.skew) != NNZ:
            raise ValueError(f"keys/{name}.json: {NNZ} fields expected")
        if max(self.cards[:N_INT]) > 1000 or max(self.cards) >= 1 << 32:
            # integer tokens are 1..3 digits, categorical ones 32 bits
            raise ValueError(f"keys/{name}.json: a field is too wide")
        self.int_hash = np.array(
            [cityhash64_short(str(v).encode())
             for v in range(max(self.cards[:N_INT]))], np.uint64)

    def draws(self, rng, n):
        """(n, 39) value ids: field f's are ranks - 1 of a power law over
        its `cards[f]` values, p(rank) ~ the integral of x^-skew over
        [rank, rank + 1), drawn by inverting its distribution function."""
        out = np.empty((n, NNZ), dtype=np.uint64)
        for f, (card, s) in enumerate(zip(self.cards, self.skew)):
            u = rng.random(n)
            a = 1.0 - s
            if abs(a) < 1e-9:
                x = np.exp(u * np.log(card + 1.0))
            else:
                x = (1.0 + u * ((card + 1.0) ** a - 1.0)) ** (1.0 / a)
            out[:, f] = np.minimum(x.astype(np.uint64), _U(card)) - _U(1)
        return out


def token32(draws):
    """The 32-bit token of a categorical value: a bijection of its id
    salted by the field (add, multiply by an odd number and xor-shift are
    each one-to-one mod 2^32), so a field's V values are V distinct
    8-hex-digit tokens, like the hashed tokens of the real set."""
    m = _U(0xFFFFFFFF)
    x = (draws + (np.arange(N_INT, NNZ, dtype=np.uint64)
                  * _U(0x9E3779B9))) & m
    x = (x * _U(0x85EBCA6B)) & m
    x ^= x >> _U(15)
    x = (x * _U(0xC2B2AE35)) & m
    x ^= x >> _U(13)
    return x


def mix_field_values(draws):
    """64-bit value per (field, draw): per-field salt then a splitmix-style
    mix, so a categorical token is unique to its field and value."""
    with np.errstate(over="ignore"):  # 64-bit mixing wraps by design
        x = draws + (np.arange(draws.shape[1], dtype=np.uint64)
                     * _U(0x9E3779B97F4A7C15))
        x ^= x >> _U(30)
        x *= _U(0xBF58476D1CE4E5B9)
        x ^= x >> _U(27)
    return x


# ------------------------------------------------------------- CityHash64
# The Criteo format's key of a token in field f is
# (CityHash64(token) >> 10) | (f << 54) (upstream criteo_parser.h:69-82).
# Tokens here are 1-2 decimal digits or 8 hex digits, so only the
# 1..3-byte and the 8-byte cases of CityHash v1.1's HashLen0to16 occur.
def _rotr(v, s):
    return (v >> _U(s)) | (v << _U(64 - s))


def _hashlen16(u, v, mul):
    with np.errstate(over="ignore"):
        a = (u ^ v) * mul
        a ^= a >> _U(47)
        b = (v ^ a) * mul
        b ^= b >> _U(47)
        return b * mul


def cityhash64_len8(words):
    """CityHash64 of 8-byte strings given as little-endian uint64."""
    with np.errstate(over="ignore"):
        mul = _U(K2) + _U(16)
        a = words + _U(K2)
        b = words
        c = _rotr(b, 37) * mul + a
        d = (_rotr(a, 25) + b) * mul
    return _hashlen16(c, d, mul)


def cityhash64_short(s: bytes) -> int:
    """CityHash64 of a 1..3-byte string (plain Python integers)."""
    n = len(s)
    assert 0 < n < 4
    y = (s[0] + (s[n >> 1] << 8)) & _M
    z = (n + (s[n - 1] << 2)) & _M
    v = ((y * K2) & _M) ^ ((z * K0) & _M)
    v ^= v >> 47
    return (v * K2) & _M


def hex_words(v32):
    """uint64 array of 32-bit values -> the 8 ASCII bytes of their %08x,
    as little-endian uint64 (byte 0 is the first character)."""
    x = v32.astype(np.uint64)
    x = (x | (x << _U(16))) & _U(0x0000FFFF0000FFFF)
    x = (x | (x << _U(8))) & _U(0x00FF00FF00FF00FF)
    x = (x | (x << _U(4))) & _U(0x0F0F0F0F0F0F0F0F)
    x = x.byteswap()                      # highest nibble first
    ge10 = ((x + _U(0x0606060606060606)) >> _U(4)) & _U(0x0101010101010101)
    return x + _U(0x3030303030303030) + ge10 * _U(0x27)


def criteo_keys(draws, cat32, int_hash):
    """(n, 39) uint64 feature keys of the rows' tokens, as the Criteo
    format defines them."""
    h = np.empty(draws.shape, np.uint64)
    h[:, :N_INT] = int_hash[draws[:, :N_INT].astype(np.intp)]
    h[:, N_INT:] = cityhash64_len8(hex_words(cat32))
    field = np.arange(NNZ, dtype=np.uint64) << _U(54)
    return (h >> _U(10)) | field


# ------------------------------------------------------------------- rows
class Rows:
    """One file part's rows: the draws, the tokens' keys and the labels."""

    def __init__(self, model: KeyModel, seed: int, stream: int, part: int,
                 n: int):
        rng = np.random.default_rng([int(seed), int(stream), int(part)])
        self.model = model
        self.draws = model.draws(rng, n)
        mixed = mix_field_values(self.draws)
        wt = (mixed >> _U(40)).astype(np.float64) / 2.0**24 - 0.5
        margin = PLANT_BIAS + PLANT_SCALE * wt.sum(axis=1)
        self.label = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))
                      ).astype(np.float32)
        # integer tokens are the bare value (like the real data, where
        # the same integer in two fields is the same token)
        self.cat32 = token32(self.draws[:, N_INT:])
        self.n = n

    def keys(self):
        return criteo_keys(self.draws, self.cat32, self.model.int_hash)

    def text(self) -> bytes:
        """Criteo text: label, 13 ints, 26 hex categoricals, tabs. Built
        as a fixed-width byte matrix whose unused cells (the leading
        digits of a short integer) are dropped at the end."""
        n = self.n
        width = 2 + 4 * N_INT + 9 * (NNZ - N_INT)
        out = np.zeros((n, width), np.uint8)
        tab = ord("\t")
        out[:, 0] = self.label.astype(np.uint8) + ord("0")
        out[:, 1] = tab
        ints = self.draws[:, :N_INT].astype(np.uint16)
        hundreds, tens = ints // 100, ints // 10
        cols = 2 + 4 * np.arange(N_INT)
        out[:, cols] = np.where(hundreds > 0, hundreds + ord("0"), 0)
        out[:, cols + 1] = np.where(tens > 0, tens % 10 + ord("0"), 0)
        out[:, cols + 2] = ints % 10 + ord("0")
        out[:, cols + 3] = tab
        base = 2 + 4 * N_INT
        cat = out[:, base:].reshape(n, NNZ - N_INT, 9)
        cat[:, :, :8] = np.ascontiguousarray(
            hex_words(self.cat32)).view(np.uint8).reshape(n, -1, 8)
        cat[:, :, 8] = tab
        out[:, -1] = ord("\n")
        return out[out != 0].tobytes()


def write_part(path: str, rows: Rows, fmt: str, record_rows: int) -> None:
    """One file part in the traffic's format: `criteo` text, or `crb`
    records of `record_rows` rows through the program's own writer (the
    documented convert-once path, apps/convert.py)."""
    if fmt == "criteo":
        with open(path, "wb") as fh:
            fh.write(rows.text())
        return
    if fmt != "crb":
        raise ValueError(f"no writer for data format {fmt!r}")
    from wormhole_tpu.data.crb import write_crb
    from wormhole_tpu.data.rowblock import RowBlock

    keys = rows.keys()

    def blocks():
        for a in range(0, rows.n, record_rows):
            b = min(a + record_rows, rows.n)
            yield RowBlock(
                label=rows.label[a:b],
                offset=np.arange(0, (b - a) * NNZ + 1, NNZ, dtype=np.int64),
                index=keys[a:b].reshape(-1), value=None)

    write_crb(path, blocks())


TRAIN_STREAM, VAL_STREAM = 0, 1


class Dataset:
    """The files of one run and what is known about their batches: which
    (part, batch) has which labels, and each train batch's keys."""

    def __init__(self, root: str, model: KeyModel, seed: int, fmt: str,
                 minibatch: int, train_parts: int, batches_per_part: int,
                 val_parts: int):
        self.root, self.seed, self.fmt = root, int(seed), fmt
        self.model = model
        self.minibatch = minibatch
        self.train_parts, self.val_parts = train_parts, val_parts
        self.batches_per_part = batches_per_part
        self.rows_per_part = minibatch * batches_per_part
        self.ext = ext = "criteo" if fmt == "criteo" else "crb"
        self.by_label: dict[bytes, tuple[int, int]] = {}
        self._keys: dict[tuple[int, int], np.ndarray] = {}
        self._labels: dict[tuple[int, int], np.ndarray] = {}

        def train_part(p):
            rows = Rows(model, self.seed, TRAIN_STREAM, p,
                        self.rows_per_part)
            write_part(os.path.join(root, f"train-{p:03d}.{ext}"), rows,
                       fmt, minibatch)
            return p, rows.keys(), rows.label

        def val_part(p):
            rows = Rows(model, self.seed, VAL_STREAM, p, minibatch)
            write_part(os.path.join(root, f"val-{p:03d}.{ext}"), rows, fmt,
                       minibatch)

        # the draws, the hashing and zlib all release the interpreter
        # lock: parts are made side by side
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(min(train_parts + val_parts,
                                    os.cpu_count() or 2)) as pool:
            vals = [pool.submit(val_part, p) for p in range(val_parts)]
            for p, keys, label in pool.map(train_part, range(train_parts)):
                for j in range(batches_per_part):
                    sl = slice(j * minibatch, (j + 1) * minibatch)
                    self._keys[p, j] = keys[sl]
                    self._labels[p, j] = label[sl]
                    self.by_label[label[sl].tobytes()] = (p, j)
            for f in vals:
                f.result()
        self.train_rows = self.rows_per_part * train_parts
        self.val_rows = minibatch * val_parts
        self.links = 0

    @property
    def train_pattern(self) -> str:
        return os.path.join(self.root, r"train-\d+\." + self.ext)

    @property
    def val_pattern(self) -> str:
        return os.path.join(self.root, r"val-\d+\." + self.ext)

    @property
    def long_pattern(self) -> str:
        """The distinct parts and every further name they are linked
        under: one long pass."""
        return os.path.join(self.root, r"(train|again)-\d+\." + self.ext)

    def link_until(self, rows: int) -> int:
        """Link the distinct train parts under further names until one
        pass over `long_pattern` holds at least `rows` rows."""
        k = 0
        while self.train_rows * (1 + self.links) < rows:
            self.links += 1
            for p in range(self.train_parts):
                os.symlink(
                    os.path.join(self.root, f"train-{p:03d}.{self.ext}"),
                    os.path.join(self.root, f"again-{k:05d}.{self.ext}"))
                k += 1
        return self.links

    def batch(self, part: int, j: int):
        """Batch j of train part `part` as the format defines it: the
        (rows, 39) uint64 keys and the labels."""
        return self._keys[part, j], self._labels[part, j]
