"""Just enough of the frontend side of PostgreSQL protocol 3.0, written
from the protocol's specification (simple query, text results). Copied
from ``chip_smoke.py``; imports nothing of the program."""

from __future__ import annotations

import socket
import struct


class PgError(RuntimeError):
    """The server answered a statement with an ErrorResponse."""


class PgClient:
    def __init__(self, port: int, host: str = "127.0.0.1",
                 user: str = "bench", timeout: float = 600.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        params = (b"user\x00" + user.encode()
                  + b"\x00database\x00postgres\x00\x00")
        self.sock.sendall(
            struct.pack("!II", len(params) + 8, 196608) + params)
        while True:
            t, body = self._message()
            if t == b"E":
                raise PgError(body)
            if t == b"Z":
                return

    def _exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            c = self.sock.recv(n - len(buf))
            if not c:
                raise ConnectionError("server closed")
            buf += c
        return buf

    def _message(self):
        t = self._exact(1)
        (ln,) = struct.unpack("!I", self._exact(4))
        return t, self._exact(ln - 4)

    def query(self, sql: str):
        """One statement; returns (column names, rows of text or None),
        every row fetched and decoded."""
        q = sql.encode() + b"\x00"
        self.sock.sendall(b"Q" + struct.pack("!I", len(q) + 4) + q)
        names, rows, error = [], [], None
        while True:
            t, body = self._message()
            if t == b"T":
                (n,) = struct.unpack("!H", body[:2])
                off = 2
                for _ in range(n):
                    end = body.index(b"\x00", off)
                    names.append(body[off:end].decode())
                    off = end + 19
            elif t == b"D":
                (n,) = struct.unpack("!H", body[:2])
                off, row = 2, []
                for _ in range(n):
                    (ln,) = struct.unpack("!i", body[off:off + 4])
                    off += 4
                    if ln == -1:
                        row.append(None)
                    else:
                        row.append(body[off:off + ln].decode())
                        off += ln
                rows.append(row)
            elif t == b"E":
                error = body    # ReadyForQuery still follows
            elif t == b"Z":
                if error is not None:
                    raise PgError(error)
                return names, rows

    def close(self) -> None:
        try:
            self.sock.sendall(b"X" + struct.pack("!I", 4))
        finally:
            self.sock.close()
