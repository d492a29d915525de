"""Open-loop HTTP load generator: one process, one thread, no JAX.

Started by the harness before the window opens.  It writes ``ready``
and reads one JSON job from standard input::

    {"host": ..., "port": ..., "t_start": <time.monotonic() of due 0>,
     "requests": [[due_s, path, tenant], ...], "keep": [index, ...],
     "deadline_s": <seconds after t_start when waiting stops>}

and sends every request at its due time whatever the server's state
(asyncio, one connection per request, ``Connection: close`` so no
server worker idles on a kept-alive socket).  Latency runs from the due
time to the last byte, so a stall counts against every request behind
it; ``late`` is how long after its due time a request was sent.

It then writes one JSON line to standard output, ``{"records": [[status,
late_s, latency_s, nbytes, crc32], ...]}`` (status 0: no answer before
the deadline), followed by the bodies of the ``keep`` requests, each as
an 8-byte big-endian length and the bytes.
"""

from __future__ import annotations

import asyncio
import json
import struct
import sys
import time
import zlib


async def _get(host: str, port: int, path: str, tenant: str):
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write((f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
                      f"X-Tenant: {tenant}\r\nConnection: close\r\n\r\n"
                      ).encode("latin-1"))
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        length = None
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            name, _, value = line.decode("latin-1").partition(":")
            if name.strip().lower() == "content-length":
                length = int(value.strip())
        body = (await reader.readexactly(length) if length is not None
                else await reader.read())
        return status, body
    finally:
        writer.close()


async def _run(job):
    host, port = job["host"], int(job["port"])
    t_start, deadline = float(job["t_start"]), float(job["deadline_s"])
    keep = set(job["keep"])
    records = [None] * len(job["requests"])
    bodies = {}

    async def one(i, due, path, tenant):
        await asyncio.sleep(max(0.0, t_start + due - time.monotonic()))
        sent = time.monotonic()
        left = t_start + deadline - sent
        try:
            status, body = await asyncio.wait_for(
                _get(host, port, path, tenant), timeout=max(left, 0.001))
        except (asyncio.TimeoutError, OSError, ValueError, IndexError,
                asyncio.IncompleteReadError):
            status, body = 0, b""
        done = time.monotonic()
        records[i] = [status, sent - (t_start + due), done - (t_start + due),
                      len(body), zlib.crc32(body)]
        if i in keep:
            bodies[i] = body

    await asyncio.gather(*(one(i, float(d), p, t)
                           for i, (d, p, t) in enumerate(job["requests"])))
    return records, bodies


def main() -> int:
    sys.stdout.buffer.write(b"ready\n")
    sys.stdout.buffer.flush()
    job = json.loads(sys.stdin.readline())
    records, bodies = asyncio.run(_run(job))
    out = sys.stdout.buffer
    out.write(json.dumps({"records": records,
                          "kept": sorted(bodies)}).encode() + b"\n")
    for i in sorted(bodies):
        out.write(struct.pack(">Q", len(bodies[i])))
        out.write(bodies[i])
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
