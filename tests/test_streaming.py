"""Streaming data plane tests (cmd/erasure-encode.go:80-107 block loop,
cmd/erasure-decode.go:229-246 ranged decode, ShardFileOffset
cmd/erasure-coding.go:134).

Covers: block-batched streaming PUT through put_object_stream, ranged GET
via get_object_reader touching only covering blocks, shard-failure
fallback mid-stream, multipart part streaming, and an O(batch) memory
bound proven in a subprocess with a 512 MiB object.
"""

import hashlib
import io
import os
import subprocess
import sys

import pytest

from minio_tpu.objectlayer import erasure_object as eo
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.storage.xl_storage import XLStorage

from . import shard_files

BS = 4096          # tiny block size so a small object spans many blocks


class CountingDisk:
    """StorageAPI proxy counting read_file_stream calls/bytes."""

    def __init__(self, inner):
        self._inner = inner
        self.stream_reads = 0
        self.stream_bytes = 0

    def read_file_stream(self, volume, path, offset, length):
        self.stream_reads += 1
        self.stream_bytes += length
        return self._inner.read_file_stream(volume, path, offset, length)

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.fixture()
def layer(tmp_path, monkeypatch):
    monkeypatch.setattr(eo, "STREAM_BATCH_BYTES", 2 * BS)  # 2 blocks/batch
    disks = []
    for i in range(6):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(CountingDisk(XLStorage(str(d))))
    lay = ErasureObjects(disks, parity=2, block_size=BS, backend="numpy",
                         inline_threshold=512)
    lay.make_bucket("strbkt")
    return lay


def pattern(n: int) -> bytes:
    return (b"0123456789abcdef" * (n // 16 + 1))[:n]


def test_streaming_put_roundtrip(layer):
    body = pattern(50 * BS + 777)       # many batches + tail block
    oi = layer.put_object_stream("strbkt", "big", io.BytesIO(body))
    assert oi.size == len(body)
    assert oi.etag == hashlib.md5(body).hexdigest()
    info, got = layer.get_object("strbkt", "big")
    assert got == body
    # bytes path > batch routes through the same streaming pipeline
    oi2 = layer.put_object("strbkt", "big2", body)
    assert oi2.etag == oi.etag
    assert layer.get_object("strbkt", "big2")[1] == body


def test_streamed_matches_buffered_layout(layer):
    """A streamed PUT and a buffered PUT of the same bytes must produce
    bit-identical shard files (framing is per block, batch-invariant)."""
    body = pattern(7 * BS + 123)
    layer.put_object_stream("strbkt", "s", io.BytesIO(body))
    layer._put_object_bytes("strbkt", "b", body,
                            eo.PutObjectOptions())
    # the streamed object has a part.1 file on each drive; the buffered
    # one, at this size, is an extent of the drive's segment file
    for d in layer.disks:
        framed = shard_files.read_shard(d, "strbkt", "s")
        assert framed and framed == shard_files.read_shard(d, "strbkt", "b")


def test_range_get_touches_only_covering_blocks(layer):
    body = pattern(200 * BS)
    layer.put_object_stream("strbkt", "ranged", io.BytesIO(body))
    for d in layer.disks:
        d.stream_reads = d.stream_bytes = 0
    off, ln = 150 * BS + 100, 1000
    info, gen = layer.get_object_reader("strbkt", "ranged", off, ln)
    got = b"".join(gen)
    assert got == body[off:off + ln]
    total = sum(d.stream_bytes for d in layer.disks)
    # the range covers 1 block; with 2-block batches each of the 4 data
    # shards reads ~2 framed shard-blocks — nowhere near the full file
    sfsize = 200 * (BS // 4)
    assert 0 < total < 6 * sfsize // 10, total


def test_range_get_all_offsets(layer):
    body = pattern(9 * BS + 321)
    layer.put_object_stream("strbkt", "edges", io.BytesIO(body))
    size = len(body)
    for off, ln in [(0, 1), (0, size), (size - 1, 1), (BS - 1, 2),
                    (BS, BS), (3 * BS + 5, 4 * BS), (size - 100, 100),
                    (0, -1), (5, size)]:
        info, gen = layer.get_object_reader("strbkt", "edges", off, ln)
        want_ln = size - off if ln < 0 else min(ln, size - off)
        assert b"".join(gen) == body[off:off + want_ln], (off, ln)


def test_stream_survives_shard_loss(layer):
    body = pattern(30 * BS + 11)
    layer.put_object_stream("strbkt", "healme", io.BytesIO(body))
    # wipe two shard files (parity tolerance is 2)
    import glob
    killed = 0
    for d in layer.disks:
        if killed == 2:
            break
        for f in glob.glob(os.path.join(d.root, "strbkt", "healme",
                                        "*", "part.1")):
            os.remove(f)
            killed += 1
    assert killed == 2
    info, gen = layer.get_object_reader("strbkt", "healme")
    assert b"".join(gen) == body


def test_stream_detects_bitrot_midfile(layer):
    body = pattern(40 * BS)
    layer.put_object_stream("strbkt", "rot", io.BytesIO(body))
    # flip one byte mid-shard-file on one drive: the stream must fall
    # back to parity and still return correct bytes
    import glob
    f = glob.glob(os.path.join(layer.disks[0].root, "strbkt", "rot",
                               "*", "part.1"))[0]
    blob = bytearray(open(f, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(f, "wb").write(bytes(blob))
    info, gen = layer.get_object_reader("strbkt", "rot")
    assert b"".join(gen) == body


def test_multipart_streamed_parts(layer):
    uid = layer.new_multipart_upload("strbkt", "mpobj")
    p1 = pattern(11 * BS + 5)
    p2 = pattern(4 * BS)[::-1]
    pi1 = layer.put_object_part("strbkt", "mpobj", uid, 1, io.BytesIO(p1))
    pi2 = layer.put_object_part("strbkt", "mpobj", uid, 2, io.BytesIO(p2))
    assert pi1.etag == hashlib.md5(p1).hexdigest()
    layer.enforce_min_part_size = False
    layer.complete_multipart_upload("strbkt", "mpobj", uid,
                                    [(1, pi1.etag), (2, pi2.etag)])
    info, gen = layer.get_object_reader("strbkt", "mpobj")
    assert b"".join(gen) == p1 + p2
    # range spanning the part boundary
    off = len(p1) - 1000
    info, gen = layer.get_object_reader("strbkt", "mpobj", off, 2000)
    assert b"".join(gen) == (p1 + p2)[off:off + 2000]


class _FailingReader:
    """Reader that dies after yielding some bytes (peer hangup)."""

    def __init__(self, data: bytes, fail_after: int):
        self.buf = io.BytesIO(data)
        self.left = fail_after

    def read(self, n: int = -1) -> bytes:
        if self.left <= 0:
            raise IOError("peer hung up")
        take = min(n if n > 0 else self.left, self.left)
        self.left -= take
        return self.buf.read(take)


def test_part_retry_failure_preserves_good_part(layer):
    """A failed retry of an already-uploaded part must not corrupt it:
    parts stage under a unique name and promote atomically."""
    uid = layer.new_multipart_upload("strbkt", "retryobj")
    p1 = pattern(12 * BS)
    pi1 = layer.put_object_part("strbkt", "retryobj", uid, 1,
                                io.BytesIO(p1))
    # retry of part 1 dies mid-stream
    with pytest.raises(Exception):
        layer.put_object_part("strbkt", "retryobj", uid, 1,
                              _FailingReader(pattern(12 * BS)[::-1],
                                             5 * BS))
    # the original upload of part 1 is still intact and completes
    layer.enforce_min_part_size = False
    layer.complete_multipart_upload("strbkt", "retryobj", uid,
                                    [(1, pi1.etag)])
    assert layer.get_object("strbkt", "retryobj")[1] == p1


def test_empty_and_inline_objects(layer):
    layer.put_object("strbkt", "empty", b"")
    assert layer.get_object("strbkt", "empty")[1] == b""
    layer.put_object("strbkt", "tiny", b"inline me")   # < inline threshold
    info, gen = layer.get_object_reader("strbkt", "tiny", 2, 4)
    assert b"".join(gen) == b"line"


@pytest.fixture()
def server(tmp_path, monkeypatch):
    import minio_tpu.s3.server as s3srv
    from minio_tpu.s3.server import S3Server
    monkeypatch.setattr(eo, "STREAM_BATCH_BYTES", 4 * BS)
    monkeypatch.setattr(s3srv, "STREAM_PUT_THRESHOLD", 16 * 1024)
    disks = []
    for i in range(6):
        d = tmp_path / f"sd{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    lay = ErasureObjects(disks, parity=2, block_size=BS, backend="numpy")
    srv = S3Server(lay, access_key="sk", secret_key="ss-secret")
    srv.start()
    yield srv
    srv.stop()


def test_http_streaming_put_and_range_get(server):
    """A >threshold PUT rides the streaming path end to end over real
    HTTP (SigV4 signed-sha body), and a Range GET streams back only the
    covering blocks with correct Content-Range."""
    from minio_tpu.s3.client import S3Client
    c = S3Client(server.endpoint, "sk", "ss-secret")
    c.make_bucket("httpstr")
    body = pattern(37 * BS + 99)          # > 16 KiB threshold
    r = c.request("PUT", "/httpstr/big", body=body)
    assert r.status == 200
    want_etag = hashlib.md5(body).hexdigest()
    assert r.headers.get("ETag", "").strip('"') == want_etag

    full = c.get_object("httpstr", "big")
    assert full.body == body

    r = c.request("GET", "/httpstr/big",
                  headers={"Range": f"bytes={5 * BS + 7}-{9 * BS}"})
    assert r.status == 206
    assert r.body == body[5 * BS + 7: 9 * BS + 1]
    assert r.headers["Content-Range"] == \
        f"bytes {5 * BS + 7}-{9 * BS}/{len(body)}"

    # suffix range
    r = c.request("GET", "/httpstr/big",
                  headers={"Range": "bytes=-1000"})
    assert r.status == 206 and r.body == body[-1000:]


def test_http_streaming_put_bad_digest(server):
    """A streamed PUT whose sha256 doesn't match the body must fail with
    BadDigest and NOT leave a committed object behind."""
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.sigv4 import sign_request
    import http.client
    import urllib.parse
    c = S3Client(server.endpoint, "sk", "ss-secret")
    c.make_bucket("digbkt")
    body = pattern(20 * BS)
    url = server.endpoint + "/digbkt/bad"
    # sign over the WRONG sha (declared != actual): signature passes,
    # body hash check at EOF must reject before commit
    hdrs = sign_request(c._creds, "PUT", url, {}, b"not the body",
                        c.region)
    u = urllib.parse.urlsplit(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
    conn.request("PUT", "/digbkt/bad", body=body, headers=hdrs)
    resp = conn.getresponse()
    out = resp.read()
    assert resp.status == 400 and b"BadDigest" in out, (resp.status, out)
    conn.close()
    with pytest.raises(Exception):
        server.layer.get_object_info("digbkt", "bad")


def test_http_streaming_aws_chunked(server):
    """aws-chunked body above the stream threshold rides the incremental
    ChunkedStreamReader (per-chunk signature chain, never buffered)."""
    import http.client
    import urllib.parse
    from minio_tpu.s3 import sigv4
    from minio_tpu.s3.client import S3Client
    c = S3Client(server.endpoint, "sk", "ss-secret")
    c.make_bucket("awschk")
    data = pattern(33 * BS + 17)
    url = f"{server.endpoint}/awschk/streamed.bin"
    hdrs, body = sigv4.sign_request_streaming(
        sigv4.Credentials("sk", "ss-secret"), "PUT", url, {}, data,
        chunk_size=16 * 1024)
    u = urllib.parse.urlsplit(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
    conn.request("PUT", "/awschk/streamed.bin", body=body, headers=hdrs)
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    resp.read()
    conn.close()
    assert c.get_object("awschk", "streamed.bin").body == data

    # tampered mid-chunk: per-chunk chain must reject
    bad = bytearray(body)
    bad[len(bad) // 2] ^= 1
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
    conn.request("PUT", "/awschk/bad.bin", body=bytes(bad), headers=hdrs)
    resp = conn.getresponse()
    assert resp.status in (400, 403), resp.status
    resp.read()
    conn.close()


def test_http_streaming_multipart(server):
    from minio_tpu.s3.client import S3Client
    c = S3Client(server.endpoint, "sk", "ss-secret")
    c.make_bucket("mpstr")
    r = c.request("POST", "/mpstr/obj", query="uploads")
    import xml.etree.ElementTree as ET
    ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
    uid = r.xml().find(f"{ns}UploadId").text
    p1 = pattern(21 * BS)
    p2 = pattern(6 * BS)[::-1]
    etags = []
    for num, p in ((1, p1), (2, p2)):
        r = c.request("PUT", "/mpstr/obj",
                      query=f"partNumber={num}&uploadId={uid}", body=p)
        etags.append(r.headers["ETag"])
    server.layer.enforce_min_part_size = False
    parts_xml = "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>{e}</ETag></Part>"
        for n, e in zip((1, 2), etags))
    r = c.request("POST", "/mpstr/obj", query=f"uploadId={uid}",
                  body=(f"<CompleteMultipartUpload>{parts_xml}"
                        "</CompleteMultipartUpload>").encode())
    assert r.status == 200
    assert c.get_object("mpstr", "obj").body == p1 + p2


_RSS_SCRIPT = r"""
import io, os, resource, sys
sys.path.insert(0, {repo!r})
import numpy as np
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.storage.xl_storage import XLStorage

tmp = {tmp!r}
disks = []
for i in range(4):
    d = os.path.join(tmp, f"d{{i}}")
    os.makedirs(d, exist_ok=True)
    disks.append(XLStorage(d))
layer = ErasureObjects(disks, parity=2, block_size=1024*1024,
                       backend="numpy")
layer.make_bucket("membkt")

SIZE = 512 * 1024 * 1024
CHUNK = 1 * 1024 * 1024
seed_block = (b"0123456789abcdef" * (CHUNK // 16))

class Source:
    def __init__(self):
        self.left = SIZE
    def read(self, n):
        take = min(n, self.left, CHUNK)
        if take <= 0:
            return b""
        self.left -= take
        return seed_block[:take]

rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
oi = layer.put_object_stream("membkt", "huge", Source())
assert oi.size == SIZE, oi.size
import hashlib
h = hashlib.md5()
left = SIZE
while left:
    t = min(left, CHUNK)
    h.update(seed_block[:t])
    left -= t
assert oi.etag == h.hexdigest()

# stream the whole object back, consuming chunk by chunk
info, gen = layer.get_object_reader("membkt", "huge")
g = hashlib.md5()
n = 0
for chunk in gen:
    g.update(chunk)
    n += len(chunk)
assert n == SIZE and g.hexdigest() == oi.etag

# ranged GET of 1 MiB from the middle
info, gen = layer.get_object_reader("membkt", "huge",
                                    SIZE // 2 + 12345, 1024 * 1024)
got = b"".join(gen)
assert len(got) == 1024 * 1024

peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
growth_mib = (peak - rss0) / 1024.0   # ru_maxrss is KiB on linux
print(f"RSS growth {{growth_mib:.1f}} MiB")
assert growth_mib < 256, f"peak RSS grew {{growth_mib:.1f}} MiB"
print("MEM OK")
"""


@pytest.mark.slow
def test_memory_bounded_512mib(tmp_path):
    """VERDICT item 1 'done' gate: a large object round-trips and a 1 MiB
    range-GET completes with peak RSS growth < 256 MiB."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _RSS_SCRIPT.format(repo=repo, tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MT_STREAM_BATCH=str(16 * 1024 * 1024), MT_FSYNC="0")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr + res.stdout
    assert "MEM OK" in res.stdout, res.stdout
