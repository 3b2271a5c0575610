"""A minimal RESP client (stdlib only; no import of the program)."""

from __future__ import annotations

import socket


def pack(*words: bytes) -> bytes:
    out = [b"*%d\r\n" % len(words)]
    for w in words:
        out.append(b"$%d\r\n%s\r\n" % (len(w), w))
    return b"".join(out)


class Err(str):
    """An error reply (``-...``); a value, not an exception, so that a
    caller can count it."""


class Parser:
    """Incremental reply parser: ``feed`` bytes, ``pop`` complete replies."""

    def __init__(self) -> None:
        self.buf = bytearray()
        self.pos = 0

    def feed(self, data: bytes) -> None:
        if self.pos and self.pos == len(self.buf):
            del self.buf[:]
            self.pos = 0
        self.buf += data

    def pop(self):
        """One complete reply, or ``Parser.MORE`` when the buffer holds
        only part of one."""
        start = self.pos
        out = self._one()
        if out is Parser.MORE:
            self.pos = start
        elif self.pos > (1 << 20):
            del self.buf[: self.pos]
            self.pos = 0
        return out

    MORE = object()

    def _line(self):
        i = self.buf.find(b"\r\n", self.pos)
        if i < 0:
            return None
        line = bytes(self.buf[self.pos : i])
        self.pos = i + 2
        return line

    def _one(self):
        line = self._line()
        if line is None:
            return Parser.MORE
        kind, rest = line[:1], line[1:]
        if kind == b"+":
            return rest
        if kind == b":":
            return int(rest)
        if kind == b"-":
            return Err(rest.decode(errors="replace"))
        if kind == b"$":
            n = int(rest)
            if n < 0:
                return None
            if len(self.buf) - self.pos < n + 2:
                return Parser.MORE
            out = bytes(self.buf[self.pos : self.pos + n])
            self.pos += n + 2
            return out
        if kind == b"*":
            n = int(rest)
            if n < 0:
                return None
            items = []
            for _ in range(n):
                item = self._one()
                if item is Parser.MORE:
                    return Parser.MORE
                items.append(item)
            return items
        raise ValueError(f"unparseable reply line: {line[:80]!r}")


class Conn:
    """One blocking connection: ``call`` a command, or ``pipeline`` many."""

    def __init__(self, host: str, port: int, timeout: float = 120.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.parser = Parser()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        self.sock.close()

    def read(self):
        while True:
            out = self.parser.pop()
            if out is not Parser.MORE:
                return out
            chunk = self.sock.recv(1 << 18)
            if not chunk:
                raise ConnectionError("connection closed by the server")
            self.parser.feed(chunk)

    def call(self, *words: bytes):
        self.sock.sendall(pack(*words))
        return self.read()

    def pipeline(self, commands: list[bytes], chunk: int = 512) -> list:
        """Send packed commands in chunks, return their replies in order."""
        out = []
        for i in range(0, len(commands), chunk):
            part = commands[i : i + chunk]
            self.sock.sendall(b"".join(part))
            out.extend(self.read() for _ in part)
        return out
