"""Data layer tests: parsers, RowBlock, MinibatchIter, CRB, match_file,
config. The unit layer the reference lacks (SURVEY.md §4)."""

import os

import numpy as np
import pytest

from wormhole_tpu.config import load_config, parse_conf_text
from wormhole_tpu.data import crb
from wormhole_tpu.data.match_file import match_file
from wormhole_tpu.data.minibatch import MinibatchIter, _take_rows
from wormhole_tpu.data.parsers import (
    iter_file_chunks,
    parse_adfea,
    parse_criteo,
    parse_libsvm,
)
from wormhole_tpu.data.rowblock import RowBlock, to_device_batch
from wormhole_tpu.ops.hashing import cityhash64, pack_field_key, reverse_bytes_u64


# ---------------------------------------------------------------- hashing
def test_cityhash64_stable():
    # regression pins for our implementation
    assert cityhash64("") == 0x9AE16A3B2F90404F
    vecs = {len(s): cityhash64(s) for s in ["a", "abcd", "12345678",
                                           "x" * 20, "y" * 40, "z" * 70]}
    assert len(set(vecs.values())) == len(vecs)  # all distinct


def test_cityhash64_avalanche():
    a, b = cityhash64("feature_1"), cityhash64("feature_2")
    assert bin(a ^ b).count("1") > 16


def test_pack_field_key():
    k = pack_field_key("deadbeef", 5)
    assert k >> 54 == 5
    assert pack_field_key("deadbeef", 1023) >> 54 == 1023


def test_reverse_bytes():
    x = np.array([0x0102030405060708], dtype=np.uint64)
    assert reverse_bytes_u64(x)[0] == 0x0807060504030201
    seq = np.arange(1000, dtype=np.uint64)
    rev = reverse_bytes_u64(seq)
    assert len(np.unique(rev)) == 1000  # bijective
    np.testing.assert_array_equal(reverse_bytes_u64(rev), seq)


# ---------------------------------------------------------------- parsers
def test_parse_libsvm():
    blk = parse_libsvm("1 3:1 10:2.5\n0 1:1\n# comment\n-1 5:1\n")
    assert blk.size == 3
    assert blk.nnz == 4
    np.testing.assert_array_equal(blk.label, [1, 0, -1])
    np.testing.assert_array_equal(blk.index, [3, 10, 1, 5])
    np.testing.assert_array_equal(blk.value, [1, 2.5, 1, 1])


def test_parse_libsvm_binary_compaction():
    blk = parse_libsvm("1 3:1 10:1\n0 1:1\n")
    assert blk.value is None  # all-ones value array dropped


def test_parse_criteo():
    line = "1\t5\t\t12\t" + "\t".join(["a93bc2f1"] * 26) + "\n"
    blk = parse_criteo(line)
    assert blk.size == 1
    assert blk.label[0] == 1
    # 2 present ints (one field empty) + 26 cats
    assert blk.nnz == 28
    fields = (blk.index >> np.uint64(54)).astype(int)
    assert fields[0] == 0 and fields[1] == 2  # field ids packed in top bits
    # identical categorical tokens in different fields get different keys
    assert len(np.unique(blk.index[2:])) == 26


def test_parse_criteo_test_mode():
    line = "5\t\t12\t" + "\t".join(["a93bc2f1"] * 26) + "\n"
    blk = parse_criteo(line, has_label=False)
    assert blk.size == 1 and blk.label[0] == 0 and blk.nnz == 28


def test_parse_adfea():
    blk = parse_adfea("100 3 1 12345:3 678:3 999:7\n101 1 0 12345:3\n")
    assert blk.size == 2
    np.testing.assert_array_equal(blk.label, [1, 0])
    assert (blk.index[0] >> np.uint64(54)) == 3
    assert blk.index[0] == blk.index[3]  # same fid:gid -> same key


# ---------------------------------------------------------------- rowblock
def test_rowblock_slice_concat():
    blk = parse_libsvm("1 1:2\n0 2:3 3:4\n1 4:5\n0 5:6 6:7 7:8\n")
    a, b = blk.slice(0, 2), blk.slice(2, 4)
    back = RowBlock.concat([a, b])
    np.testing.assert_array_equal(back.label, blk.label)
    np.testing.assert_array_equal(back.offset, blk.offset)
    np.testing.assert_array_equal(back.index, blk.index)
    np.testing.assert_array_equal(back.value, blk.value)


def test_take_rows_permutation():
    blk = parse_libsvm("1 1:2\n0 2:3 3:4\n1 4:5\n")
    perm = _take_rows(blk, np.array([2, 0, 1]))
    np.testing.assert_array_equal(perm.label, [1, 1, 0])
    np.testing.assert_array_equal(perm.index, [4, 1, 2, 3])
    np.testing.assert_array_equal(perm.value, [5, 2, 3, 4])


def test_device_batch_padding():
    blk = parse_libsvm("1 3:1 10:2.5\n0 1:1\n")
    db = to_device_batch(blk, num_rows=4, capacity=8, num_buckets=16)
    assert db.val[3:].sum() == 0  # padding contributes nothing
    np.testing.assert_array_equal(db.row_mask, [1, 1, 0, 0])
    np.testing.assert_array_equal(db.idx[:3], [3, 10, 1])


def test_device_batch_truncation():
    blk = parse_libsvm("1 1:1 2:1 3:1\n0 4:1\n")
    db = to_device_batch(blk, num_rows=1, capacity=2, num_buckets=16)
    assert db.num_rows == 1 and db.capacity == 2


# ---------------------------------------------------------------- splits
def test_input_split_disjoint_cover(tmp_path):
    p = tmp_path / "d.txt"
    lines = [f"{i} {i}:1" for i in range(997)]
    p.write_text("\n".join(lines) + "\n")
    got = []
    for part in range(4):
        for chunk in iter_file_chunks(str(p), part, 4):
            got += chunk.splitlines()
    assert got == lines  # disjoint and complete, in order


def test_minibatch_iter_sizes(synth_libsvm_file):
    mbs = list(MinibatchIter(synth_libsvm_file, 0, 1, "libsvm",
                             minibatch_size=100))
    assert [m.size for m in mbs] == [100, 100, 100, 100, 100, 12]


def test_minibatch_iter_parts_cover(synth_libsvm_file):
    total = sum(
        m.size
        for part in range(3)
        for m in MinibatchIter(synth_libsvm_file, part, 3, "libsvm",
                               minibatch_size=64)
    )
    assert total == 512


def test_minibatch_shuffle_preserves_rows(synth_libsvm_file):
    plain = list(MinibatchIter(synth_libsvm_file, 0, 1, "libsvm",
                               minibatch_size=64))
    shuf = list(MinibatchIter(synth_libsvm_file, 0, 1, "libsvm",
                              minibatch_size=64, shuf_buf=200, seed=7))
    tot = RowBlock.concat(plain)
    tot_s = RowBlock.concat(shuf)
    assert tot_s.size == tot.size and tot_s.nnz == tot.nnz
    assert not np.array_equal(tot_s.label, tot.label)  # actually shuffled
    assert sorted(tot_s.index.tolist()) == sorted(tot.index.tolist())


def test_neg_sampling(synth_libsvm_file):
    full = RowBlock.concat(list(MinibatchIter(synth_libsvm_file,
                                              minibatch_size=64)))
    samp = RowBlock.concat(
        list(MinibatchIter(synth_libsvm_file, minibatch_size=64,
                           neg_sampling=0.2, seed=3))
    )
    n_pos_full = int((full.label > 0).sum())
    n_pos_samp = int((samp.label > 0).sum())
    assert n_pos_samp == n_pos_full  # positives always kept
    assert (samp.size - n_pos_samp) < (full.size - n_pos_full) * 0.5


# ---------------------------------------------------------------- crb
def test_crb_roundtrip(tmp_path, synth_libsvm_file):
    mbs = list(MinibatchIter(synth_libsvm_file, minibatch_size=100))
    path = str(tmp_path / "d.crb")
    assert crb.write_crb(path, mbs) == len(mbs)
    back = list(crb.read_crb(path))
    assert len(back) == len(mbs)
    for a, b in zip(back, mbs):
        np.testing.assert_array_equal(a.label, b.label)
        np.testing.assert_array_equal(a.index, b.index)
        np.testing.assert_array_equal(
            a.value if a.value is not None else [],
            b.value if b.value is not None else [])


def test_crb_parts(tmp_path, synth_libsvm_file):
    mbs = list(MinibatchIter(synth_libsvm_file, minibatch_size=50))
    path = str(tmp_path / "d.crb")
    crb.write_crb(path, mbs)
    n = sum(b.size for part in range(3) for b in crb.read_crb(path, part, 3))
    assert n == 512


def test_crb_via_minibatch_iter(tmp_path, synth_libsvm_file):
    mbs = list(MinibatchIter(synth_libsvm_file, minibatch_size=50))
    path = str(tmp_path / "d.crb")
    crb.write_crb(path, mbs)
    out = list(MinibatchIter(path, fmt="crb", minibatch_size=128))
    assert sum(m.size for m in out) == 512
    assert [m.size for m in out[:-1]] == [128] * (len(out) - 1)


# ---------------------------------------------------------------- files
def test_match_file(tmp_path):
    for i in range(5):
        (tmp_path / f"part-{i}.txt").write_text("x")
    (tmp_path / "other.dat").write_text("x")
    got = match_file(str(tmp_path / r"part-\d+\.txt"))
    assert len(got) == 5
    exact = match_file(str(tmp_path / "other.dat"))
    assert exact == [str(tmp_path / "other.dat")]


# ---------------------------------------------------------------- config
def test_config_merge(tmp_path):
    import dataclasses
    from typing import Optional

    @dataclasses.dataclass
    class Conf:
        train_data: str = ""
        val_data: Optional[str] = None
        minibatch: int = 1000
        lr_eta: float = 0.1
        lambda_l1: float = 0.0
        algo: str = "ftrl"
        shuffle: bool = False

    p = tmp_path / "demo.conf"
    p.write_text(
        "train_data = data/train\n"
        "minibatch = 500  # comment\n"
        'algo = "sgd"\n'
        "lambda_l1 = 4\n"
    )
    cfg = load_config(Conf, str(p), ["minibatch=250", "shuffle=true"])
    assert cfg.train_data == "data/train"
    assert cfg.minibatch == 250  # CLI wins
    assert cfg.algo == "sgd"
    assert cfg.lambda_l1 == 4.0
    assert cfg.shuffle is True
    with pytest.raises(ValueError):
        load_config(Conf, None, ["nonexistent_key=1"])


def test_parse_conf_repeated():
    kv = parse_conf_text("a = 1\na = 2\nb = x\n")
    assert kv["a"] == ["1", "2"]


def test_config_repeated_field_accumulates(tmp_path):
    import dataclasses

    @dataclasses.dataclass
    class Conf:
        val_data: list = dataclasses.field(default_factory=list)

    Conf.__dataclass_fields__["val_data"].type = "list[str]"
    p = tmp_path / "c.conf"
    p.write_text("val_data = a\nval_data = b\n")
    cfg = load_config(Conf, str(p), ["val_data=c"])
    assert cfg.val_data == ["a", "b", "c"]  # CLI appends for repeated fields


def test_minibatch_early_abandon_no_thread_leak(synth_libsvm_file):
    import threading
    import gc

    before = threading.active_count()
    for _ in range(20):
        it = iter(MinibatchIter(synth_libsvm_file, minibatch_size=16))
        next(it)  # peek one batch, abandon
        del it
    gc.collect()
    deadline = 50  # producer poll interval is 0.2s
    import time
    while threading.active_count() > before and deadline:
        time.sleep(0.1)
        deadline -= 1
    assert threading.active_count() <= before + 1


def test_agaricus_parses(agaricus):
    train, test = agaricus
    blk = RowBlock.concat(list(MinibatchIter(train, minibatch_size=1000)))
    assert blk.size > 1500
    assert set(np.unique(blk.label)) <= {0.0, 1.0}
    assert blk.value is None  # agaricus is binary -> compacted


# ------------------------------------------------------------- filesys
class _MemFS:
    """In-memory filesystem registered under a test scheme — proves any
    remote backend plugged into data/filesys makes matching, InputSplit
    reads, and CRB IO remote-capable at once."""

    def __init__(self):
        self.files: dict[str, bytes] = {}

    def open(self, path, mode="rb"):
        import io

        if "r" in mode:
            data = self.files[path]
            return (io.BytesIO(data) if "b" in mode
                    else io.StringIO(data.decode()))
        fsref = self

        class _W(io.BytesIO):
            def close(self_inner):
                prev = fsref.files.get(path, b"") if "a" in mode else b""
                fsref.files[path] = prev + self_inner.getvalue()
                super().close()

        return _W()

    def list_dir(self, path):
        path = path.rstrip("/") + "/"
        return sorted({f[len(path):].split("/", 1)[0]
                       for f in self.files if f.startswith(path)})

    def isfile(self, path):
        return path in self.files

    def isdir(self, path):
        return any(f.startswith(path.rstrip("/") + "/") for f in self.files)

    def getsize(self, path):
        return len(self.files[path])


def test_filesys_uri_scheme_roundtrip():
    from wormhole_tpu.data import filesys as fsys
    from wormhole_tpu.data.match_file import match_file
    from wormhole_tpu.data.parsers import iter_file_chunks

    mem = _MemFS()
    fsys.register_filesystem("memtest", mem)
    lines = "".join(f"1 {i}:1\n" for i in range(100)).encode()
    with fsys.open_stream("memtest://bucket/data/part-0", "wb") as f:
        f.write(lines)
    with fsys.open_stream("memtest://bucket/data/part-1", "wb") as f:
        f.write(lines)
    # match_file over the remote scheme
    got = match_file("memtest://bucket/data/part-.*")
    assert got == ["memtest://bucket/data/part-0",
                   "memtest://bucket/data/part-1"]
    # InputSplit over the remote scheme: both halves partition the lines
    c0 = "".join(iter_file_chunks("memtest://bucket/data/part-0", 0, 2))
    c1 = "".join(iter_file_chunks("memtest://bucket/data/part-0", 1, 2))
    assert (c0 + c1).encode() == lines
    assert c0 and c1


# ---------------------------------------------------- InputSplit chunks
def _line_end(data: bytes, at: int) -> int:
    """The first line boundary at-or-after byte offset `at` (> 0)."""
    nl = data.find(b"\n", at - 1)
    return nl + 1 if nl >= 0 else len(data)


def _split_chunks(data: bytes, part: int, num_parts: int,
                  chunk_bytes: int) -> list[str]:
    """The InputSplit rule on the file's bytes: a part starts at the
    first line beginning at-or-after its range's start; a chunk closes
    at the first line end at-or-past `chunk_bytes` or the range's end."""
    size = len(data)
    begin, end = size * part // num_parts, size * (part + 1) // num_parts
    pos = _line_end(data, begin) if begin else 0
    out = []
    while pos < end:
        stop = _line_end(data, pos + min(chunk_bytes, end - pos))
        out.append(data[pos:stop].decode("utf-8", errors="replace"))
        pos = stop
    return out


_LINES = b"".join(b"%d %d:1 %d:0.5\n" % (i % 2, i, i * i)
                  for i in range(400))
_SPLIT_FILES = {
    "final_newline": _LINES,
    "no_final_newline": _LINES[:-1],
    "long_line": _LINES[:2000] + b"1 " + b"7:1 " * 3000 + b"\n"
                 + _LINES[2000:],
    "crlf": _LINES.replace(b"\n", b"\r\n"),
    "fewer_lines_than_parts": b"1 1:1\n0 2:1\n1 3:1",
    "empty": b"",
}


@pytest.mark.parametrize("scheme", ["local", "memtest"])
@pytest.mark.parametrize("chunk_bytes", [16, 64, 4096, 1 << 24])
@pytest.mark.parametrize("num_parts", [1, 2, 3, 7])
@pytest.mark.parametrize("kind", sorted(_SPLIT_FILES))
def test_iter_file_chunks_equals_split_rule(tmp_path, kind, num_parts,
                                            chunk_bytes, scheme):
    from wormhole_tpu.data import filesys as fsys

    data = _SPLIT_FILES[kind]
    if scheme == "local":
        path = str(tmp_path / "d.txt")
        with open(path, "wb") as f:
            f.write(data)
    else:
        mem = _MemFS()
        fsys.register_filesystem("memtest", mem)
        path = "memtest://bucket/d.txt"
        mem.files["bucket/d.txt"] = data
    got = [list(iter_file_chunks(path, k, num_parts, chunk_bytes))
           for k in range(num_parts)]
    assert got == [_split_chunks(data, k, num_parts, chunk_bytes)
                   for k in range(num_parts)]
    # the parts partition the file, in order
    assert "".join(c for part in got for c in part) == data.decode()


def test_iter_file_chunks_calls_do_not_grow_with_lines():
    """A chunk costs one block read and at most one readline, whatever
    the number of lines in it: each per-line `readline` or `tell` on a
    buffered file drops the interpreter lock (PERF.md §6, PR 25)."""
    import collections
    import io

    from wormhole_tpu.data import filesys as fsys

    calls = collections.Counter()

    class _Counting(io.BytesIO):
        def read(self, *a):
            calls["read"] += 1
            return super().read(*a)

        def readline(self, *a):
            calls["readline"] += 1
            return super().readline(*a)

        def tell(self):
            calls["tell"] += 1
            return super().tell()

    class _CountingFS(_MemFS):
        def open(self, path, mode="rb"):
            return _Counting(self.files[path])

    mem = _CountingFS()
    fsys.register_filesystem("counttest", mem)
    per_chunk = {}
    for lines in (2000, 8000):
        data = b"".join(b"1 %d:1 %d:1\n" % (i, i + 1) for i in range(lines))
        mem.files["b/d.txt"] = data
        num_parts, chunk_bytes = 3, 4096
        calls.clear()
        chunks = sum(
            len(list(iter_file_chunks("counttest://b/d.txt", part,
                                      num_parts, chunk_bytes)))
            for part in range(num_parts))
        assert chunks > len(data) // (chunk_bytes + 64)
        assert (calls["readline"] + calls["tell"]
                <= 2 * chunks + 2 * num_parts)
        assert calls["read"] <= chunks + num_parts
        per_chunk[lines] = (calls["readline"] + calls["tell"]) / chunks
    # four times the lines, the same calls a chunk
    assert per_chunk[8000] <= per_chunk[2000] + 0.5


def test_filesys_crb_over_remote_scheme(tmp_path):
    from wormhole_tpu.data import filesys as fsys
    from wormhole_tpu.data.crb import read_crb, write_crb
    from wormhole_tpu.data.parsers import parse_libsvm

    fsys.register_filesystem("memtest2", _MemFS())
    blk = parse_libsvm("1 1:2 3:4\n0 2:1\n")
    write_crb("memtest2://b/x.crb", [blk])
    got = list(read_crb("memtest2://b/x.crb"))
    assert sum(b.size for b in got) == 2


class _FakeS3Client:
    """Just enough of the boto3 S3 client surface for S3FS: objects live
    in a dict keyed (bucket, key); list_objects_v2 paginates with
    ContinuationToken to exercise the pagination loop."""

    def __init__(self):
        self.objects: dict[tuple[str, str], bytes] = {}

    def get_object(self, Bucket, Key):
        import io

        return {"Body": io.BytesIO(self.objects[(Bucket, Key)])}

    def put_object(self, Bucket, Key, Body):
        self.objects[(Bucket, Key)] = bytes(Body)

    def head_object(self, Bucket, Key):
        if (Bucket, Key) not in self.objects:
            err = Exception(f"head_object 404 {Key}")
            err.response = {"Error": {"Code": "404"}}
            raise err
        return {"ContentLength": len(self.objects[(Bucket, Key)])}

    def list_objects_v2(self, Bucket, Prefix, ContinuationToken=None):
        keys = sorted(k for b, k in self.objects
                      if b == Bucket and k.startswith(Prefix))
        start = int(ContinuationToken or 0)
        page = keys[start:start + 2]  # force pagination
        resp = {"Contents": [{"Key": k} for k in page]}
        if start + 2 < len(keys):
            resp["NextContinuationToken"] = str(start + 2)
        return resp


def test_filesys_s3_adapter_over_fake_client():
    """s3:// resolves through the registry with the boto3-shaped adapter
    (reference reads S3 natively, doc/common/input.rst:53-115)."""
    from wormhole_tpu.data import filesys as fsys
    from wormhole_tpu.data.match_file import match_file

    fsys.register_filesystem("s3", fsys.S3FS(client=_FakeS3Client()))
    try:
        for i in range(5):  # >2 objects so list_objects_v2 paginates
            with fsys.open_stream(f"s3://bkt/data/part-{i}", "wb") as f:
                f.write(b"1 1:1\n")
        assert match_file("s3://bkt/data/part-.*") == [
            f"s3://bkt/data/part-{i}" for i in range(5)]
        with fsys.open_stream("s3://bkt/data/part-0", "rb") as f:
            assert f.read() == b"1 1:1\n"
        assert fsys.isfile("s3://bkt/data/part-0")
        assert not fsys.isfile("s3://bkt/data/part-9")
        assert fsys.isdir("s3://bkt/data")
        assert fsys.getsize("s3://bkt/data/part-0") == 6
    finally:
        fsys._REGISTRY.pop("s3", None)


def test_filesys_unbound_scheme_guides():
    import pytest as _pytest

    from wormhole_tpu.data import filesys as fsys

    with _pytest.raises(NotImplementedError, match="register_filesystem"):
        fsys.open_stream("hdfs://nn/host/file", "rb")
    with _pytest.raises(ValueError, match="unknown filesystem scheme"):
        fsys.get_filesystem("weird-scheme://x")


def test_checkpoint_over_remote_scheme():
    """Model save/load round-trips through a registered remote filesystem
    (reference iter_solver.h:104-119 writes shards to HDFS/S3 URIs)."""
    import numpy as np

    from wormhole_tpu.data import filesys as fsys
    from wormhole_tpu.utils.checkpoint import atomic_savez, load_parts

    fsys.register_filesystem("memckpt", _MemFS())
    atomic_savez("memckpt://b/model_part-0", w=np.arange(4.0))
    atomic_savez("memckpt://b/model_part-1", w=np.arange(4.0, 8.0))
    got = load_parts("memckpt://b/model")
    np.testing.assert_array_equal(got["w"], np.arange(8.0))
