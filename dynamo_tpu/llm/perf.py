"""Event record/replay.

Role of the reference's `recorder.rs` (JSONL event recorder) and
`kv_router/recorder.rs` (KV-event record + replay into an indexer).
(Per-stream token arrivals are the frontend's histograms and the request
ledger; no recorder of them lives here.)

- `JsonlRecorder` appends timestamped events to a JSONL file and
  `replay_jsonl` streams them back.
- `record_kv_events` subscribes a control plane's `kv_events` subject
  into a JSONL file; `replay_kv_events` feeds a recording back into a
  KvRouter/KvIndexer — reproducing a production routing state offline.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# JSONL event recording


class JsonlRecorder:
    """Append-only timestamped JSONL event log (reference recorder.rs)."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._f = open(path, "a")
        self.count = 0

    def record(self, kind: str, payload: dict) -> None:
        self._f.write(json.dumps({
            "ts": time.time(), "kind": kind, "payload": payload}) + "\n")
        self.count += 1

    def flush(self) -> None:
        self._f.flush()

    def close(self) -> None:
        self._f.close()


def replay_jsonl(path: str):
    """Yield (ts, kind, payload) tuples from a recording."""
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            d = json.loads(line)
            yield d["ts"], d["kind"], d["payload"]


# ---------------------------------------------------------------------------
# KV-event record/replay (kv_router/recorder.rs)


async def record_kv_events(cp, path: str,
                           subject: str = "kv_events") -> asyncio.Task:
    """Subscribe `kv_events` into a JSONL file; returns the pump task
    (cancel it to stop; the recorder is flushed per event)."""
    rec = JsonlRecorder(path)
    sub = await cp.subscribe(subject)

    async def pump():
        try:
            while True:
                payload = await sub.next()
                rec.record("kv_event", payload)
                rec.flush()
        except (asyncio.CancelledError, ConnectionError):
            raise
        finally:
            sub.cancel()
            rec.close()

    return asyncio.create_task(pump())


def replay_kv_events(path: str, router) -> int:
    """Apply a recording to a KvRouter (or anything with `apply_event`);
    returns the number of events applied.  Rebuilds the exact radix-index
    state a production run had — offline routing analysis."""
    from dynamo_tpu.llm.kv_router.protocols import RouterEvent

    n = 0
    for _, kind, payload in replay_jsonl(path):
        if kind != "kv_event":
            continue
        router.apply_event(RouterEvent.from_dict(payload))
        n += 1
    return n
