"""KV pool: share of the pool's blocks that live requests held while each
decode chunk ran (``blocks_used`` of the engine's ``decode_chunk`` events
over the pool's blocks), averaged over the window's chunks.  The decode
chunk copies the whole stacked pool, so the unused share is copy traffic
that does no work.  Silent where the events carry no ``blocks_used``.
Moves ``tpot_p90_ms``."""


def read(rec: dict):
    evs = [e for e in rec.get("decode_chunk_events") or []
           if "blocks_used" in e]
    if not evs:
        return None
    blocks = rec["mix"]["engine"]["num_blocks"]
    return 100.0 * sum(ev["blocks_used"] for ev in evs) / (blocks * len(evs))
