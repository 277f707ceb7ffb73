"""S3 gateway backend: the object layer proxied to a remote S3 service.

Reference: cmd/gateway/s3/gateway-s3.go — every object operation maps to
the corresponding remote S3 call (minio-go there, the repo's own SigV4
client here); listings page through remote ListObjectsV2; multipart
passes straight through.  Bucket metadata (policy/lifecycle/...), IAM
and server config live on a LOCAL metadata directory, exactly like the
reference gateway keeps its config in its own store.
"""

from __future__ import annotations

import io
import urllib.parse
import xml.etree.ElementTree as ET
from typing import Iterator

from minio_tpu.erasure.listing import ListEntry
from minio_tpu.erasure.objects import (
    ObjectInfo, PutObjectOptions, open_by_info,
)
from minio_tpu.erasure.multipart import PartInfo
from minio_tpu.storage import errors
from minio_tpu.storage.api import VolInfo
from minio_tpu.storage.local import SYSTEM_VOL, LocalStorage
from minio_tpu.utils.s3client import S3Client, S3ClientError

_NS = "{http://s3.amazonaws.com/doc/2006-03-01/}"

# internal metadata (SSE envelopes, compression markers) survives the
# remote round trip as namespaced user metadata — dropping it would turn
# SSE/compressed uploads into unreadable ciphertext/frames on GET
_INTERNAL_PFX = "x-minio-internal-"
_WIRE_PFX = "x-amz-meta-mtpu-int-"


def _meta_to_wire(meta: dict) -> dict:
    out = {}
    for k, v in meta.items():
        if k == "etag":
            # transformed uploads (compression) carry the ORIGINAL-bytes
            # ETag in metadata; the remote's etag is of the frames
            out[_WIRE_PFX + "etag"] = str(v)
        elif k.startswith("x-amz-meta-"):
            out[k] = v
        elif k.startswith(_INTERNAL_PFX):
            import base64

            raw = v.encode() if isinstance(v, str) else bytes(v)
            out[_WIRE_PFX + k[len(_INTERNAL_PFX):]] = \
                base64.b64encode(raw).decode()
    return out


def _meta_from_wire(headers: dict) -> dict:
    out = {}
    for k, v in headers.items():
        lk = k.lower()
        if lk.startswith(_WIRE_PFX):
            import base64

            try:
                out[_INTERNAL_PFX + lk[len(_WIRE_PFX):]] = \
                    base64.b64decode(v).decode("utf-8")
            except Exception:
                continue
        elif lk.startswith("x-amz-meta-"):
            out[lk] = v
    return out


def _text(el, tag: str, default: str = "") -> str:
    t = el.findtext(f"{_NS}{tag}")
    if t is None:
        t = el.findtext(tag)
    return t if t is not None else default


def _parse_http_date(s: str) -> float:
    import email.utils

    try:
        return email.utils.parsedate_to_datetime(s).timestamp()
    except Exception:
        return 0.0


def _parse_iso(s: str) -> float:
    import datetime as dt

    try:
        return dt.datetime.fromisoformat(
            s.replace("Z", "+00:00")).timestamp()
    except ValueError:
        return 0.0


def _map_err(e: S3ClientError, bucket: str, obj: str = "") -> Exception:
    body = e.body.decode("utf-8", "replace") if e.body else ""
    if e.status == 404:
        if "NoSuchBucket" in body:
            return errors.BucketNotFound(bucket)
        if obj:
            return errors.ObjectNotFound(f"{bucket}/{obj}")
        return errors.BucketNotFound(bucket)
    if e.status == 409:
        if "BucketNotEmpty" in body:
            return errors.BucketNotEmpty(bucket)
        return errors.BucketExists(bucket)
    if e.status == 403:
        return errors.FileAccessDenied(f"{bucket}/{obj}")
    return errors.StorageError(f"remote returned {e.status}: {body[:200]}")


class S3Gateway:
    """Object layer over a remote S3 endpoint.

    `metadata_dir` holds everything that is NOT object data: IAM users,
    server config, bucket metadata (policies, lifecycle, ...) — the
    remote only ever sees object/bucket traffic.
    """

    def __init__(self, endpoint: str, access_key: str, secret_key: str,
                 metadata_dir: str, region: str = "us-east-1"):
        self.client = S3Client(endpoint, access_key, secret_key,
                               region=region)
        self._meta = LocalStorage(metadata_dir, endpoint="gateway-meta")

    # things the cross-cutting subsystems (IAM store, ServerConfig,
    # metrics) introspect: one pool with one metadata drive, no erasure
    # sets
    @property
    def pools(self):
        return [self]

    @property
    def all_disks(self):
        return [self._meta]

    sets: list = []

    def storage_info(self) -> dict:
        di = self._meta.disk_info()
        return {"pools": [{
            "sets": 0, "drives_per_set": 0, "deployment_id": "gateway",
            "disks": [{"endpoint": self.client.netloc, "total": di.total,
                       "free": di.free, "used": di.used, "online": True,
                       "id": "gateway", "healing": False}],
        }]}

    # ------------------------------------------------------------- buckets
    def make_bucket(self, bucket: str) -> None:
        try:
            self.client._request("PUT", bucket, ok=(200,))
        except S3ClientError as e:
            raise _map_err(e, bucket)

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        try:
            self.client._request("DELETE", bucket, ok=(200, 204))
        except S3ClientError as e:
            raise _map_err(e, bucket)
        try:
            self._meta.delete(SYSTEM_VOL, f"buckets/{bucket}",
                              recursive=True)
        except errors.StorageError:
            pass

    def bucket_exists(self, bucket: str) -> bool:
        return self.client.bucket_exists(bucket)

    def list_buckets(self) -> list[VolInfo]:
        try:
            _, _, body = self.client._request("GET", "", ok=(200,))
        except S3ClientError as e:
            raise _map_err(e, "")
        out = []
        root = ET.fromstring(body)
        for b in root.iter():
            if b.tag.endswith("Bucket"):
                out.append(VolInfo(
                    name=_text(b, "Name"),
                    created=_parse_iso(_text(b, "CreationDate"))))
        return out

    # ------------------------------------------------------------- objects
    def put_object(self, bucket: str, obj: str, reader, size: int = -1,
                   opts: PutObjectOptions | None = None) -> ObjectInfo:
        opts = opts or PutObjectOptions()
        headers = {}
        if opts.content_type:
            headers["Content-Type"] = opts.content_type
        if opts.finalize_metadata is not None:
            # transforming wrappers (compression) only know their final
            # metadata at EOF, but HTTP headers go first: buffer
            data = reader.read()
            size = len(data)
            reader = io.BytesIO(data)
        meta = dict(opts.user_metadata)
        sent = [0]
        if size < 0:
            # unknown length: stream with chunked transfer-encoding
            # instead of buffering the whole object
            def chunks():
                while True:
                    c = reader.read(1 << 20)
                    if not c:
                        return
                    sent[0] += len(c)
                    yield c
            body, length = chunks(), None
        else:
            body, length = _reader_chunks(reader, size), size
        if opts.finalize_metadata is not None:
            meta.update(opts.finalize_metadata() or {})
        headers.update(_meta_to_wire(meta))
        try:
            rh = self.client.put_object(bucket, obj, body, headers=headers,
                                        length=length)
        except S3ClientError as e:
            raise _map_err(e, bucket, obj)
        return ObjectInfo(bucket=bucket, name=obj,
                          etag=meta.get("etag",
                                        rh.get("etag", "").strip('"')),
                          size=size if size >= 0 else sent[0],
                          metadata=meta)

    def get_object_info(self, bucket: str, obj: str,
                        version_id: str = "") -> ObjectInfo:
        q = [("versionId", version_id)] if version_id else None
        try:
            _, rh, _ = self.client._request("HEAD", bucket, obj, query=q,
                                            ok=(200,))
        except S3ClientError as e:
            raise _map_err(e, bucket, obj)
        return self._oi_from_headers(bucket, obj, rh)

    def open_object(self, bucket: str, obj: str, version_id: str = ""):
        return open_by_info(self, bucket, obj, version_id)

    @staticmethod
    def _oi_from_headers(bucket: str, obj: str, rh: dict) -> ObjectInfo:
        meta = _meta_from_wire(rh)
        etag = meta.pop(_INTERNAL_PFX + "etag",
                        rh.get("etag", "").strip('"'))
        return ObjectInfo(
            bucket=bucket, name=obj,
            version_id=rh.get("x-amz-version-id", ""),
            size=int(rh.get("content-length", "0") or 0),
            etag=etag,
            content_type=rh.get("content-type", ""),
            mod_time=_parse_http_date(rh.get("last-modified", "")),
            metadata=meta)

    def get_object(self, bucket: str, obj: str, offset: int = 0,
                   length: int = -1, version_id: str = ""
                   ) -> tuple[ObjectInfo, Iterator[bytes]]:
        if length == 0:
            # empty read: no remote call, and no malformed bytes=0--1
            return (self.get_object_info(bucket, obj, version_id),
                    iter(()))
        headers = {}
        if offset or length >= 0:
            end = "" if length < 0 else str(offset + length - 1)
            headers["Range"] = f"bytes={offset}-{end}"
        try:
            # ONE round trip: ObjectInfo comes from the GET response
            # headers (a separate HEAD both costs a WAN RTT and races
            # overwrites)
            rh, stream = self.client.get_object_stream(
                bucket, obj, headers=headers, with_headers=True)
        except S3ClientError as e:
            raise _map_err(e, bucket, obj)
        oi = self._oi_from_headers(bucket, obj, rh)
        cr = rh.get("content-range", "")
        if "/" in cr:
            try:
                oi.size = int(cr.rsplit("/", 1)[1])
            except ValueError:
                pass
        return oi, stream

    def delete_object(self, bucket: str, obj: str, version_id: str = "",
                      versioned: bool = False,
                      suspended: bool = False) -> ObjectInfo:
        try:
            self.client.delete_object(bucket, obj, version_id)
        except S3ClientError as e:
            raise _map_err(e, bucket, obj)
        return ObjectInfo(bucket=bucket, name=obj, version_id=version_id)

    def delete_objects(self, bucket: str, dels: list[dict]) -> list:
        out = []
        for d in dels:
            try:
                out.append(self.delete_object(bucket, d["obj"],
                                              d.get("version_id", "")))
            except Exception as e:
                out.append(e)
        return out

    # ------------------------------------------------------------- listing
    def list_entries(self, bucket: str, prefix: str = "", marker: str = "",
                     include_marker: bool = False):
        """Sorted name stream for the shared listing engine, paged from
        remote ListObjectsV2 (reference gateway-s3 ListObjects)."""
        token = ""
        start_after = marker
        while True:
            q = [("list-type", "2"), ("max-keys", "1000")]
            if prefix:
                q.append(("prefix", prefix))
            if token:
                q.append(("continuation-token", token))
            elif start_after:
                q.append(("start-after", start_after))
            try:
                _, _, body = self.client._request("GET", bucket, query=q,
                                                  ok=(200,))
            except S3ClientError as e:
                raise _map_err(e, bucket)
            root = ET.fromstring(body)
            for c in root.iter():
                if not c.tag.endswith("Contents"):
                    continue
                name = _text(c, "Key")
                if not include_marker and marker and name <= marker:
                    continue
                oi = ObjectInfo(
                    bucket=bucket, name=name,
                    size=int(_text(c, "Size", "0") or 0),
                    etag=_text(c, "ETag").strip('"'),
                    mod_time=_parse_iso(_text(c, "LastModified")))
                yield ListEntry(name=name, _versions=[oi])
            if _text(root, "IsTruncated") != "true":
                return
            token = _text(root, "NextContinuationToken")
            if not token:
                return

    def list_objects(self, bucket: str, prefix: str = "") -> list[str]:
        return [e.name for e in self.list_entries(bucket, prefix=prefix)]

    # ----------------------------------------------------------- multipart
    def new_multipart_upload(self, bucket: str, obj: str,
                             opts: PutObjectOptions | None = None) -> str:
        opts = opts or PutObjectOptions()
        headers = {}
        if opts.content_type:
            headers["Content-Type"] = opts.content_type
        for k, v in opts.user_metadata.items():
            if k.startswith("x-amz-meta-"):
                headers[k] = v
        try:
            _, _, body = self.client._request(
                "POST", bucket, obj, query=[("uploads", "")],
                headers=headers, ok=(200,))
        except S3ClientError as e:
            raise _map_err(e, bucket, obj)
        uid = _text(ET.fromstring(body), "UploadId")
        if not uid:
            raise errors.StorageError("remote returned no UploadId")
        return uid

    def put_object_part(self, bucket: str, obj: str, upload_id: str,
                        part_number: int, reader, size: int = -1
                        ) -> PartInfo:
        # known size streams with Content-Length; unknown size streams
        # with Transfer-Encoding: chunked — either way the part is never
        # spooled locally (reference streams through,
        # cmd/gateway/s3/gateway-s3.go)
        sent = [0]

        def counted():
            while True:
                chunk = reader.read(1 << 20)
                if not chunk:
                    return
                sent[0] += len(chunk)
                yield chunk

        if size < 0:
            body, length = counted(), None
        else:
            body, length = _reader_chunks(reader, size), size
        try:
            _, rh, _ = self.client._request(
                "PUT", bucket, obj,
                query=[("partNumber", str(part_number)),
                       ("uploadId", upload_id)],
                body=body, length=length, ok=(200,))
        except S3ClientError as e:
            if e.status == 404:
                raise errors.InvalidArgument(
                    f"upload id {upload_id} not found")
            raise _map_err(e, bucket, obj)
        got = size if size >= 0 else sent[0]
        return PartInfo(part_number=part_number,
                        etag=rh.get("etag", "").strip('"'), size=got)

    def complete_multipart_upload(self, bucket: str, obj: str,
                                  upload_id: str,
                                  parts: list[tuple[int, str]]) -> ObjectInfo:
        inner = "".join(
            f"<Part><PartNumber>{n}</PartNumber><ETag>\"{etag}\"</ETag>"
            f"</Part>" for n, etag in parts)
        body = (f"<CompleteMultipartUpload>{inner}"
                f"</CompleteMultipartUpload>").encode()
        try:
            _, _, resp = self.client._request(
                "POST", bucket, obj, query=[("uploadId", upload_id)],
                body=body, ok=(200,))
        except S3ClientError as e:
            if e.status == 404:
                raise errors.InvalidArgument(
                    f"upload id {upload_id} not found")
            raise _map_err(e, bucket, obj)
        root = ET.fromstring(resp)
        if root.tag.endswith("Error"):
            # S3 CompleteMultipartUpload may return 200 with an Error body
            raise errors.StorageError(
                f"remote complete failed: {_text(root, 'Code')} "
                f"{_text(root, 'Message')}")
        etag = _text(root, "ETag").strip('"')
        if not etag:
            raise errors.StorageError(
                "remote complete returned no ETag")
        return ObjectInfo(bucket=bucket, name=obj, etag=etag)

    def abort_multipart_upload(self, bucket: str, obj: str,
                               upload_id: str) -> None:
        try:
            self.client._request("DELETE", bucket, obj,
                                 query=[("uploadId", upload_id)],
                                 ok=(200, 204))
        except S3ClientError as e:
            if e.status == 404:
                raise errors.InvalidArgument(
                    f"upload id {upload_id} not found")
            raise _map_err(e, bucket, obj)

    def list_object_parts(self, bucket: str, obj: str,
                          upload_id: str) -> list[PartInfo]:
        try:
            _, _, body = self.client._request(
                "GET", bucket, obj, query=[("uploadId", upload_id)],
                ok=(200,))
        except S3ClientError as e:
            if e.status == 404:
                raise errors.InvalidArgument(
                    f"upload id {upload_id} not found")
            raise _map_err(e, bucket, obj)
        out = []
        for p in ET.fromstring(body).iter():
            if p.tag.endswith("Part"):
                out.append(PartInfo(
                    part_number=int(_text(p, "PartNumber", "0") or 0),
                    etag=_text(p, "ETag").strip('"'),
                    size=int(_text(p, "Size", "0") or 0)))
        return out

    # ------------------------------------------ object metadata passthrough
    def update_object_metadata(self, bucket: str, obj: str, updates: dict,
                               version_id: str = "") -> ObjectInfo:
        raise errors.MethodNotAllowed(
            "metadata updates are not supported in gateway mode")

    def put_object_tags(self, bucket, obj, tags, version_id=""):
        q = [("tagging", "")]
        if version_id:
            q.append(("versionId", version_id))
        inner = "".join(
            f"<Tag><Key>{k}</Key><Value>{v}</Value></Tag>"
            for k, v in urllib.parse.parse_qsl(tags))
        body = (f"<Tagging><TagSet>{inner}</TagSet></Tagging>").encode()
        try:
            self.client._request("PUT", bucket, obj, query=q, body=body,
                                 ok=(200,))
        except S3ClientError as e:
            raise _map_err(e, bucket, obj)
        return ObjectInfo(bucket=bucket, name=obj)

    def get_object_tags(self, bucket, obj, version_id="") -> str:
        q = [("tagging", "")]
        if version_id:
            q.append(("versionId", version_id))
        try:
            _, _, body = self.client._request("GET", bucket, obj, query=q,
                                              ok=(200,))
        except S3ClientError as e:
            raise _map_err(e, bucket, obj)
        pairs = []
        for t in ET.fromstring(body).iter():
            if t.tag.endswith("Tag"):
                pairs.append((_text(t, "Key"), _text(t, "Value")))
        return urllib.parse.urlencode(pairs)

    def delete_object_tags(self, bucket, obj, version_id=""):
        q = [("tagging", "")]
        if version_id:
            q.append(("versionId", version_id))
        try:
            self.client._request("DELETE", bucket, obj, query=q,
                                 ok=(200, 204))
        except S3ClientError as e:
            raise _map_err(e, bucket, obj)
        return ObjectInfo(bucket=bucket, name=obj)

    # --------------------------------------- LOCAL bucket metadata + config
    def _bucket_meta_path(self, bucket: str) -> str:
        return f"buckets/{bucket}/.metadata.json"

    def get_bucket_metadata(self, bucket: str) -> dict:
        import json

        try:
            return json.loads(self._meta.read_all(
                SYSTEM_VOL, self._bucket_meta_path(bucket)))
        except (errors.StorageError, ValueError):
            return {}

    def set_bucket_metadata(self, bucket: str, meta: dict) -> None:
        import json

        self._meta.write_all(SYSTEM_VOL, self._bucket_meta_path(bucket),
                             json.dumps(meta).encode())

    def update_bucket_metadata(self, bucket: str, **kv) -> None:
        meta = self.get_bucket_metadata(bucket)
        meta.update(kv)
        self.set_bucket_metadata(bucket, meta)

    def versioning_status(self, bucket: str) -> str:
        v = self.get_bucket_metadata(bucket).get("versioning")
        if v is True:
            return "Enabled"
        return v or ""

    def versioning_enabled(self, bucket: str) -> bool:
        return self.versioning_status(bucket) == "Enabled"

    def set_versioning(self, bucket: str, status) -> None:
        if isinstance(status, bool):
            status = "Enabled" if status else "Suspended"
        self.update_bucket_metadata(bucket, versioning=status)

    # ------------------------------------------------ unsupported (erasure)
    def heal_object(self, bucket, obj, version_id="", deep=False):
        raise errors.MethodNotAllowed("heal is not supported in gateway mode")

    def transition_version(self, *a, **kw):
        raise errors.MethodNotAllowed(
            "tiering is not supported in gateway mode")

    def free_space(self) -> int:
        return self._meta.disk_info().free


def _reader_chunks(reader, size: int, chunk: int = 1 << 20
                   ) -> Iterator[bytes]:
    remaining = size
    while remaining > 0:
        data = reader.read(min(chunk, remaining))
        if not data:
            break
        remaining -= len(data)
        yield data
