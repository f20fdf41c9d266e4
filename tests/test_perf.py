"""JSONL event record/replay (reference recorder.rs,
kv_router/recorder.rs)."""

import asyncio


from dynamo_tpu.llm.kv_router.protocols import (
    KvCacheEvent,
    KvCacheEventData,
    RouterEvent,
)
from dynamo_tpu.llm.kv_router.router import KvRouter, KvRouterConfig
from dynamo_tpu.llm.perf import (
    JsonlRecorder,
    replay_jsonl,
    replay_kv_events,
    record_kv_events,
)
from dynamo_tpu.runtime.control_plane import InProcessControlPlane


def test_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    rec = JsonlRecorder(path)
    rec.record("a", {"x": 1})
    rec.record("b", {"y": [1, 2]})
    rec.close()
    events = list(replay_jsonl(path))
    assert [(k, p) for _, k, p in events] == [("a", {"x": 1}),
                                             ("b", {"y": [1, 2]})]
    assert events[0][0] <= events[1][0]


def test_kv_event_record_and_replay(tmp_path):
    """Live events recorded from the control plane rebuild an identical
    router index on replay."""
    path = str(tmp_path / "kv.jsonl")

    def stored(eid, hashes, parent=None):
        return RouterEvent(worker_id=7, event=KvCacheEvent(
            event_id=eid,
            data=KvCacheEventData.stored(hashes, parent_hash=parent)))

    async def main():
        cp = InProcessControlPlane()
        await cp.start()
        task = await record_kv_events(cp, path)
        live = KvRouter(KvRouterConfig(block_size=8))
        evs = [stored(1, [101, 102]), stored(2, [103], parent=102)]
        for ev in evs:
            live.apply_event(ev)
            await cp.publish("kv_events", ev.to_dict())
        await asyncio.sleep(0.1)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        await cp.close()

        replayed = KvRouter(KvRouterConfig(block_size=8))
        assert replay_kv_events(path, replayed) == 2
        for h in ([101], [101, 102], [101, 102, 103]):
            assert (replayed.indexer.find_matches(h).scores
                    == live.indexer.find_matches(h).scores)

    asyncio.run(main())
