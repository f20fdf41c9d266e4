"""Open-loop HTTP load from one asyncio loop in the benchmark's process.

Every request of a schedule is sent at its due time whether or not earlier
ones have finished, over a connection of its own, as a streamed
`/v1/completions` call.  Each server-sent event is stamped with the
monotonic clock when its bytes arrive; a chunk's text holds one visible word
per token (see serve_child.py), so the tokens of one chunk share a time."""

from __future__ import annotations

import asyncio
import json
import time
from typing import List, Optional


async def http_get(port: int, path: str, timeout: float = 30.0):
    """(status, body bytes) of a plain GET; the server closes the stream."""
    async def go():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            writer.write((f"GET {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                          "Connection: close\r\n\r\n").encode())
            await writer.drain()
            raw = await reader.read()
        finally:
            writer.close()
        head, _, body = raw.partition(b"\r\n\r\n")
        lines = head.split(b"\r\n")
        status = int(lines[0].split()[1])
        if any(ln.lower().startswith(b"transfer-encoding: chunked")
               for ln in lines[1:]):
            body = _dechunk_all(body)
        return status, body
    return await asyncio.wait_for(go(), timeout)


def _dechunk_all(raw: bytes) -> bytes:
    out, i = [], 0
    while i < len(raw):
        j = raw.find(b"\r\n", i)
        if j < 0:
            break
        size = int(raw[i:j].split(b";")[0] or b"0", 16)
        if size == 0:
            break
        out.append(raw[j + 2:j + 2 + size])
        i = j + 2 + size + 2
    return b"".join(out)


class _Dechunker:
    """Incremental decoder of HTTP/1.1 chunked transfer encoding."""

    def __init__(self) -> None:
        self.buf = b""
        self.need = 0          # payload bytes left in the current chunk
        self.done = False

    def feed(self, data: bytes) -> bytes:
        self.buf += data
        out = []
        while self.buf and not self.done:
            if self.need > 0:
                take = self.buf[:self.need]
                out.append(take)
                self.buf = self.buf[len(take):]
                self.need -= len(take)
                if self.need == 0:
                    self.need = -2          # the CRLF after the payload
                continue
            if self.need < 0:
                if len(self.buf) < -self.need:
                    break
                self.buf = self.buf[-self.need:]
                self.need = 0
                continue
            j = self.buf.find(b"\r\n")
            if j < 0:
                break
            size = int(self.buf[:j].split(b";")[0], 16)
            self.buf = self.buf[j + 2:]
            if size == 0:
                self.done = True
            else:
                self.need = size
        return b"".join(out)


def new_record(index: int, due: float, n_in: int, n_out: int) -> dict:
    return {"index": index, "due": due, "sent": None, "first": None,
            "token_times": [], "chunks": [], "n_in": n_in, "n_out": n_out, "ok": False,
            "done": None, "status": None, "prompt_tokens": None,
            "completion_tokens": None, "finish_reason": None, "error": None,
            "cancelled": False}


async def stream_completion(port: int, model: str, prompt: str,
                            rec: dict) -> None:
    """Send one streamed completion and fill `rec` as its events arrive.
    Never raises: what went wrong lands in rec["error"]."""
    body = json.dumps({"model": model, "prompt": prompt,
                       "max_tokens": rec["n_out"], "temperature": 0,
                       "stream": True,
                       "stream_options": {"include_usage": True}}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                      "Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n"
                      "Connection: close\r\n\r\n").encode() + body)
        rec["sent"] = time.monotonic()
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        lines = head.split(b"\r\n")
        rec["status"] = int(lines[0].split()[1])
        chunked = any(ln.lower().startswith(b"transfer-encoding: chunked")
                      for ln in lines[1:])
        if rec["status"] != 200:
            rec["error"] = (await reader.read(400)).decode(errors="replace")
            return
        dechunk = _Dechunker() if chunked else None
        pending = b""
        saw_done = False
        while True:
            data = await reader.read(65536)
            now = time.monotonic()
            if not data:
                break
            pending += dechunk.feed(data) if dechunk else data
            while True:
                k = pending.find(b"\n\n")
                if k < 0:
                    break
                event, pending = pending[:k], pending[k + 2:]
                for line in event.split(b"\n"):
                    if not line.startswith(b"data: "):
                        continue
                    payload = line[6:].strip()
                    if payload == b"[DONE]":
                        saw_done = True
                        continue
                    obj = json.loads(payload)
                    if obj.get("usage"):
                        rec["prompt_tokens"] = obj["usage"]["prompt_tokens"]
                        rec["completion_tokens"] = \
                            obj["usage"]["completion_tokens"]
                    for choice in obj.get("choices") or ():
                        n = len((choice.get("text") or "").split())
                        if n:
                            if rec["first"] is None:
                                rec["first"] = now
                            rec["token_times"].extend([now] * n)
                            rec["chunks"].append([now, n])
                        if choice.get("finish_reason"):
                            rec["finish_reason"] = choice["finish_reason"]
            if saw_done:
                break
        rec["done"] = time.monotonic()
        if not saw_done:
            rec["error"] = "stream ended without [DONE]"
        elif rec["completion_tokens"] != rec["n_out"]:
            rec["error"] = (f"completion_tokens {rec['completion_tokens']} "
                            f"!= max_tokens {rec['n_out']} "
                            f"(finish_reason {rec['finish_reason']})")
        elif rec["prompt_tokens"] != rec["n_in"]:
            rec["error"] = (f"prompt_tokens {rec['prompt_tokens']} != "
                            f"{rec['n_in']} sent")
        elif len(rec["token_times"]) != rec["n_out"]:
            rec["error"] = (f"client saw {len(rec['token_times'])} tokens, "
                            f"usage says {rec['n_out']}")
        else:
            rec["ok"] = True
    except asyncio.CancelledError:
        rec["cancelled"] = True
        rec["error"] = rec["error"] or "cancelled: not finished when the drain ended"
        raise
    except Exception as e:   # a failed request is a result, not a crash
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


def prompt_text(ids: List[int]) -> str:
    return " ".join(f"w{i}" for i in ids)


def parse_prometheus(text: str) -> dict:
    """{'name{labels}': float} of a Prometheus text page."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


async def scrape(port: int) -> Optional[dict]:
    try:
        status, body = await http_get(port, "/metrics", timeout=10.0)
    except (OSError, asyncio.TimeoutError, ValueError):
        return None
    if status != 200:
        return None
    page = parse_prometheus(body.decode(errors="replace"))
    page["_t"] = time.monotonic()
    return page
