"""The load generator's own S3 client: SigV4 over one kept-alive connection.

Written here, not imported from the program: the client is part of the
yardstick.  It signs as SDKs sign over TLS and as the repo's `S3Client`
signs a streamed body: `x-amz-content-sha256: UNSIGNED-PAYLOAD` with a
Content-Length, so the client's CPU goes into sending and not into a
SHA-256 of every body (the configuration files list this under
`assumed`).
"""

from __future__ import annotations

import hashlib
import hmac
import http.client
import urllib.parse
from datetime import datetime, timezone

ACCESS_KEY, SECRET_KEY, REGION = "benchadmin", "benchsecret123", "us-east-1"
UNSIGNED = "UNSIGNED-PAYLOAD"


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def _quote(s: str, safe: str = "-_.~") -> str:
    return urllib.parse.quote(s, safe=safe)


def sign(method: str, path: str, query: list[tuple[str, str]], host: str,
         length: int | None, payload_hash: str = UNSIGNED) -> dict[str, str]:
    """Headers of one SigV4 request; the payload unsigned unless its
    SHA-256 is given (the admin API takes only signed payloads)."""
    now = datetime.now(timezone.utc).strftime("%Y%m%dT%H%M%SZ")
    date = now[:8]
    headers = {"host": host, "x-amz-date": now,
               "x-amz-content-sha256": payload_hash}
    signed = sorted(headers)
    canon_query = "&".join(f"{k}={v}" for k, v in sorted(
        (_quote(k), _quote(v)) for k, v in query))
    canon = "\n".join([
        method, _quote(path, safe="-_.~/") or "/", canon_query,
        "".join(f"{h}:{headers[h]}\n" for h in signed),
        ";".join(signed), payload_hash])
    scope = f"{date}/{REGION}/s3/aws4_request"
    to_sign = "\n".join(["AWS4-HMAC-SHA256", now, scope,
                         hashlib.sha256(canon.encode()).hexdigest()])
    key = ("AWS4" + SECRET_KEY).encode()
    for part in (date, REGION, "s3", "aws4_request"):
        key = _hmac(key, part)
    sig = hmac.new(key, to_sign.encode(), hashlib.sha256).hexdigest()
    headers["authorization"] = (
        f"AWS4-HMAC-SHA256 Credential={ACCESS_KEY}/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={sig}")
    if length is not None:
        headers["content-length"] = str(length)
    return headers


class Connection:
    """One client's connection; reopened after an error or a close."""

    def __init__(self, port: int, timeout: float = 120.0,
                 signed_payload: bool = False):
        self.signed_payload = signed_payload
        self.host = f"127.0.0.1:{port}"
        self.port = port
        self.timeout = timeout
        self._conn: http.client.HTTPConnection | None = None
        self.last_length: str | None = None  # Content-Length of the reply

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str, body: bytes | None = None,
                query: list[tuple[str, str]] | None = None,
                into: bytearray | None = None) -> tuple[int, bytes | int]:
        """One signed request -> (status, body).  With `into`, a 200's
        body is read into that buffer and its length returned instead.
        An OSError or a protocol error closes the connection and is
        raised to the caller, which counts the request as failed."""
        query = query or []
        headers = sign(
            method, path, query, self.host,
            len(body) if body is not None else None,
            hashlib.sha256(body or b"").hexdigest() if self.signed_payload
            else UNSIGNED)
        url = _quote(path, safe="-_.~/")
        if query:
            url += "?" + "&".join(
                f"{_quote(k)}={_quote(v)}" for k, v in query)
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=self.timeout)
        try:
            self._conn.request(method, url, body=body, headers=headers)
            resp = self._conn.getresponse()
            self.last_length = resp.getheader("content-length")
            if into is not None and resp.status == 200:
                view, got = memoryview(into), 0
                while got < len(into):
                    n = resp.readinto(view[got:])
                    if not n:
                        break
                    got += n
                extra = resp.read()  # b"" unless the body is too long
                data: bytes | int = got + len(extra)
            else:
                data = resp.read()
            if resp.will_close:
                self.close()
            return resp.status, data
        except (OSError, http.client.HTTPException):
            self.close()
            raise
