"""Plain SSZ for the reference: decode and hash_tree_root from the
simple-serialize spec, on hashlib and numpy.

It shares nothing with the program's `ssz` package.  Values decode to
plain Python and numpy: a uint to `int`, bytes to `bytes`, bits to a
bool array, a list or vector of uints to a uint array, of byte vectors
to an (n, size) uint8 array, of flat fixed-size containers to a
`Columns` of per-field arrays, a container to a `dict`.
"""
from __future__ import annotations

import hashlib

import numpy as np

BYTES_PER_CHUNK = 32
_DTYPES = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


ZERO_HASHES = [b"\x00" * 32]
for _ in range(64):
    ZERO_HASHES.append(sha256(ZERO_HASHES[-1] * 2))


def hash_pairs(level: np.ndarray) -> np.ndarray:
    """(2m, 32) uint8 -> (m, 32): SHA-256 of each adjacent pair."""
    raw = level.tobytes()
    out = b"".join(hashlib.sha256(raw[i:i + 64]).digest()
                   for i in range(0, len(raw), 64))
    return np.frombuffer(out, np.uint8).reshape(-1, 32)


def merkleize(chunks: np.ndarray, limit: int | None = None) -> bytes:
    """Root of (n, 32) chunks padded with zero chunks to the next power
    of two of `limit` (of n where no limit)."""
    n = len(chunks)
    width = n if limit is None else limit
    if limit is not None and n > limit:
        raise ValueError(f"{n} chunks over limit {limit}")
    depth = max(width - 1, 0).bit_length()
    if n == 0:
        return ZERO_HASHES[depth]
    level = np.asarray(chunks, np.uint8).reshape(n, 32)
    for d in range(depth):
        if len(level) % 2:
            pad = np.frombuffer(ZERO_HASHES[d], np.uint8).reshape(1, 32)
            level = np.concatenate([level, pad])
        level = hash_pairs(level)
    return level[0].tobytes()


def mix_in_length(root: bytes, length: int) -> bytes:
    return sha256(root + length.to_bytes(32, "little"))


def pack_bytes(data: bytes) -> np.ndarray:
    pad = (-len(data)) % 32
    return np.frombuffer(data + b"\x00" * pad, np.uint8).reshape(-1, 32)


# ---------------------------------------------------------------------------
# types
# ---------------------------------------------------------------------------

class Uint:
    def __init__(self, size: int):
        self.size = size

    def fixed(self):
        return self.size

    def decode(self, b):
        return int.from_bytes(b, "little")

    def root(self, v):
        return int(v).to_bytes(self.size, "little").ljust(32, b"\x00")


class Boolean(Uint):
    def __init__(self):
        super().__init__(1)

    def decode(self, b):
        if b not in (b"\x00", b"\x01"):
            raise ValueError("bad boolean")
        return b == b"\x01"


class ByteVector:
    def __init__(self, n: int):
        self.n = n

    def fixed(self):
        return self.n

    def decode(self, b):
        return bytes(b)

    def root(self, v):
        return merkleize(pack_bytes(bytes(v)))


class ByteList:
    def __init__(self, limit: int):
        self.limit = limit

    def fixed(self):
        return None

    def decode(self, b):
        if len(b) > self.limit:
            raise ValueError("byte list over limit")
        return bytes(b)

    def root(self, v):
        return mix_in_length(merkleize(pack_bytes(v), (self.limit + 31) // 32),
                             len(v))


def _bits_chunks(bits) -> np.ndarray:
    return pack_bytes(np.packbits(np.asarray(bits, bool),
                                  bitorder="little").tobytes())


class Bitvector:
    def __init__(self, n: int):
        self.n = n

    def fixed(self):
        return (self.n + 7) // 8

    def decode(self, b):
        return np.unpackbits(np.frombuffer(b, np.uint8),
                             bitorder="little")[:self.n].astype(bool)

    def root(self, v):
        return merkleize(_bits_chunks(v), (self.n + 255) // 256)


class Bitlist:
    def __init__(self, limit: int):
        self.limit = limit

    def fixed(self):
        return None

    def decode(self, b):
        if not b or b[-1] == 0:
            raise ValueError("bitlist without delimiter")
        bits = np.unpackbits(np.frombuffer(b, np.uint8), bitorder="little")
        n = len(bits) - 8 + int(b[-1]).bit_length() - 1
        if n > self.limit:
            raise ValueError("bitlist over limit")
        return bits[:n].astype(bool)

    def root(self, v):
        return mix_in_length(merkleize(_bits_chunks(v),
                                       (self.limit + 255) // 256), len(v))


class Container:
    def __init__(self, name: str, fields):
        self.name = name
        self.fields = list(fields)

    def fixed(self):
        sizes = [t.fixed() for _n, t in self.fields]
        return None if None in sizes else sum(sizes)

    def decode(self, b):
        out, offsets, pos = {}, [], 0
        for name, t in self.fields:
            size = t.fixed()
            if size is None:
                offsets.append((name, t, int.from_bytes(b[pos:pos + 4],
                                                        "little")))
                pos += 4
            else:
                out[name] = t.decode(b[pos:pos + size])
                pos += size
        ends = [o for _n, _t, o in offsets[1:]] + [len(b)]
        for (name, t, start), end in zip(offsets, ends):
            if not pos <= start <= end <= len(b):
                raise ValueError(f"{self.name}.{name}: bad offset")
            out[name] = t.decode(b[start:end])
        if not offsets and pos != len(b):
            raise ValueError(f"{self.name}: {len(b)} bytes, want {pos}")
        return out

    def root(self, v):
        leaves = b"".join(t.root(v[name]) for name, t in self.fields)
        return merkleize(np.frombuffer(leaves, np.uint8).reshape(-1, 32))


class Columns(dict):
    """A list of flat fixed-size containers, one array per field."""

    def __init__(self, count: int, columns: dict):
        super().__init__(columns)
        self.count = count

    def __len__(self):
        return self.count


def _leaf_rows(t, col, count) -> np.ndarray:
    """(count, 32) leaf chunks of one field over `count` elements."""
    if isinstance(t, Uint):
        out = np.zeros((count, 32), np.uint8)
        raw = np.asarray(col).astype(f"<u{t.size}").view(np.uint8)
        out[:, :t.size] = raw.reshape(count, t.size)
        return out
    if isinstance(t, ByteVector):
        col = np.asarray(col, np.uint8).reshape(count, t.n)
        if t.n <= 32:
            out = np.zeros((count, 32), np.uint8)
            out[:, :t.n] = col
            return out
        width = (t.n + 31) // 32
        padded = np.zeros((count, width * 32), np.uint8)
        padded[:, :t.n] = col
        level = padded.reshape(count * width, 32)
        size = 1 << (width - 1).bit_length()
        if size != width:
            full = np.zeros((count, size * 32), np.uint8)
            full[:, :width * 32] = padded
            level = full.reshape(count * size, 32)
        while size > 1:
            level = hash_pairs(level)
            size //= 2
        return level
    raise TypeError(f"no columnar leaves for {type(t).__name__}")


def _element_roots(elem, value, count) -> np.ndarray:
    """(count, 32) hash_tree_root of each composite element."""
    if isinstance(value, Columns):
        leaves = [_leaf_rows(t, value[name], count)
                  for name, t in elem.fields]
        width = 1 << (len(leaves) - 1).bit_length()
        leaves += [np.zeros((count, 32), np.uint8)] * (width - len(leaves))
        level = np.stack(leaves, axis=1).reshape(count * width, 32)
        while width > 1:
            level = hash_pairs(level)
            width //= 2
        return level
    if isinstance(elem, ByteVector) and elem.n <= 32:
        out = np.zeros((count, 32), np.uint8)
        out[:, :elem.n] = np.asarray(value, np.uint8).reshape(count, elem.n)
        return out
    if isinstance(elem, ByteVector):
        return _leaf_rows(elem, value, count)
    rows = b"".join(elem.root(v) for v in value)
    return np.frombuffer(rows, np.uint8).reshape(count, 32)


class _Sequence:
    def __init__(self, elem, length: int):
        self.elem = elem
        self.length = length

    def _decode_items(self, b, count=None):
        size = self.elem.fixed()
        if size is not None:
            if len(b) % max(size, 1):
                raise ValueError("sequence bytes not a multiple of size")
            count = len(b) // size if size else 0
            if isinstance(self.elem, Uint) and not isinstance(
                    self.elem, Boolean):
                return np.frombuffer(bytes(b), f"<u{size}").astype(
                    _DTYPES[size])
            if isinstance(self.elem, ByteVector):
                return np.frombuffer(bytes(b), np.uint8).reshape(
                    count, size).copy()
            if isinstance(self.elem, Container):
                rows = np.frombuffer(bytes(b), np.uint8).reshape(count, size)
                cols, pos = {}, 0
                for name, t in self.elem.fields:
                    w = t.fixed()
                    part = rows[:, pos:pos + w]
                    if isinstance(t, Boolean):
                        cols[name] = part[:, 0].astype(bool)
                    elif isinstance(t, Uint):
                        cols[name] = np.array(part).view(
                            f"<u{w}").reshape(count).astype(_DTYPES[w])
                    elif isinstance(t, ByteVector):
                        cols[name] = np.array(part)
                    else:
                        raise TypeError(f"nested {t} in columns")
                    pos += w
                return Columns(count, cols)
            return [self.elem.decode(b[i * size:(i + 1) * size])
                    for i in range(count)]
        if not b:
            return []
        first = int.from_bytes(b[:4], "little")
        if first % 4 or first > len(b):
            raise ValueError("bad first offset")
        offs = [int.from_bytes(b[i:i + 4], "little")
                for i in range(0, first, 4)] + [len(b)]
        return [self.elem.decode(b[offs[i]:offs[i + 1]])
                for i in range(len(offs) - 1)]

    def _chunks_root(self, v, limit_chunks):
        if isinstance(self.elem, Uint):
            raw = np.asarray(v).astype(f"<u{self.elem.size}").tobytes()
            return merkleize(pack_bytes(raw), limit_chunks)
        return merkleize(_element_roots(self.elem, v, len(v)), limit_chunks)

    def _limit_chunks(self, n):
        if isinstance(self.elem, Uint):
            return (n * self.elem.size + 31) // 32
        return n


class Vector(_Sequence):
    def fixed(self):
        size = self.elem.fixed()
        return None if size is None else size * self.length

    def decode(self, b):
        v = self._decode_items(b)
        if len(v) != self.length:
            raise ValueError(f"vector of {len(v)}, want {self.length}")
        return v

    def root(self, v):
        return self._chunks_root(v, self._limit_chunks(self.length))


class List(_Sequence):
    def fixed(self):
        return None

    def decode(self, b):
        v = self._decode_items(b)
        if len(v) > self.length:
            raise ValueError("list over limit")
        return v

    def root(self, v):
        return mix_in_length(
            self._chunks_root(v, self._limit_chunks(self.length)), len(v))


class Unused(List):
    """A list of operations the reference does not implement: it decodes
    only when empty, so a block that carries one is refused."""

    def __init__(self, limit: int, what: str):
        super().__init__(None, limit)
        self.what = what

    def decode(self, b):
        if b:
            raise NotImplementedError(f"reference: {self.what} not modelled")
        return []

    def root(self, v):
        if len(v):
            raise NotImplementedError(f"reference: {self.what} not modelled")
        return mix_in_length(ZERO_HASHES[max(self.length - 1, 0)
                                         .bit_length()], 0)
