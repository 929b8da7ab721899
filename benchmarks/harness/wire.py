"""A plain MySQL-protocol client (4.1, text protocol) over one socket.

The benchmark's own copy of `chip_smoke.WireClient`: the clock of every
end-to-end number runs around `query()`, from the send of COM_QUERY to the
last row packet.  A server error is returned, not raised, so that a refused
statement counts under `failed`.
"""

from __future__ import annotations

import socket
import struct


class ServerError(Exception):
    def __init__(self, code: int, message: str):
        super().__init__(f"server error {code}: {message}")
        self.code = code


class WireClient:
    def __init__(self, host: str, port: int, db: str = "test",
                 timeout_s: float = 900.0):
        self.sock = socket.create_connection((host, port), timeout=timeout_s)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.seq = 0
        self._recv()  # server greeting
        caps = 0x0200 | 0x8000 | 0x0008  # PROTO41 | SECURE_CONN | WITH_DB
        resp = struct.pack("<II", caps, 1 << 24) + bytes([33]) + b"\x00" * 23
        resp += b"root\x00" + b"\x00" + db.encode() + b"\x00"
        self._send(resp)
        ok = self._recv()
        if ok[0] != 0x00:
            raise ConnectionError(f"handshake refused: {ok!r}")

    def _read(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("connection closed")
            buf += chunk
        return bytes(buf)

    def _recv(self) -> bytes:
        payload = b""
        while True:  # a payload of 2^24-1 bytes continues in the next packet
            hdr = self._read(4)
            n = hdr[0] | (hdr[1] << 8) | (hdr[2] << 16)
            self.seq = hdr[3] + 1
            payload += self._read(n)
            if n < 0xFFFFFF:
                return payload

    def _send(self, payload: bytes):
        self.sock.sendall(len(payload).to_bytes(3, "little")
                          + bytes([self.seq & 0xFF]) + payload)
        self.seq += 1

    @staticmethod
    def _lenenc(buf: bytes, pos: int):
        """(value or None for NULL, next position)."""
        b = buf[pos]
        if b < 0xFB:
            return b, pos + 1
        if b == 0xFB:
            return None, pos + 1
        width = {0xFC: 2, 0xFD: 3, 0xFE: 8}[b]
        return int.from_bytes(buf[pos + 1: pos + 1 + width], "little"), \
            pos + 1 + width

    def query(self, sql: str):
        """(column type codes, rows of str-or-None tuples).  Raises
        ServerError when the server answers with an error packet."""
        self.seq = 0
        self._send(b"\x03" + sql.encode())
        first = self._recv()
        if first[0] == 0x00:
            return [], []
        if first[0] == 0xFF:
            code = struct.unpack_from("<H", first, 1)[0]
            raise ServerError(code, first[9:].decode("utf8", "replace"))
        ncols, _ = self._lenenc(first, 0)
        types = []
        for _ in range(ncols):
            col = self._recv()
            pos = 0
            for _ in range(6):  # catalog, schema, table, org_table, name, org_name
                n, pos = self._lenenc(col, pos)
                pos += n
            types.append(col[pos + 1 + 2 + 4])  # 0x0c, charset, length, TYPE
        self._recv()  # EOF after the column definitions
        rows = []
        while True:
            pkt = self._recv()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return types, rows
            pos, row = 0, []
            for _ in range(ncols):
                n, pos = self._lenenc(pkt, pos)
                if n is None:
                    row.append(None)
                else:
                    row.append(pkt[pos: pos + n].decode())
                    pos += n
            rows.append(tuple(row))

    def close(self):
        try:
            self.seq = 0
            self._send(b"\x01")  # COM_QUIT
        finally:
            self.sock.close()
