"""Step dispatch: who writes a streamed chunk, and how many one wake of
the writer carries. Since PR 54 the engine's `emit` leaves a stream's
deltas for the server's one `cake-stream-writer` thread and signals it
once where the span closes; a step record carries `stream_chunks` (the
deltas its `emit` left for the writer), `stream_direct` (deltas handed
to a per-token callback, or to a stream the writer gave back to its
handler thread) and `stream_wakes` (signals to the writer)
(`obs/steps`, `flight.add_stream`). Over the window's records, with c =
the sum of `stream_chunks`, d of `stream_direct`, w of `stream_wakes`:

- `stream_writer_share_pct` = 100 c / (c + d): 100 while no stream
  fell back to its handler thread;
- `stream_chunks_per_wake` = c / w: the rows a step streams for, where
  every row's token makes a chunk.

The emit of a step follows its record, so the sums are one step apart
from the window at each end. A program whose records have no such
field (before PR 54) reports nothing."""

DISPATCH = "step dispatch"

METRICS = [
    {"name": "stream_writer_share_pct", "unit": "%", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "stream_chunks_per_wake", "unit": "chunks", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_counter"},
]


def read(run):
    steps = run.get("steps", [])
    if not any("stream_chunks" in s or "stream_direct" in s for s in steps):
        return {}
    chunks = sum(s.get("stream_chunks", 0) for s in steps)
    direct = sum(s.get("stream_direct", 0) for s in steps)
    wakes = sum(s.get("stream_wakes", 0) for s in steps)
    out = {}
    if chunks + direct:
        out["stream_writer_share_pct"] = 100.0 * chunks / (chunks + direct)
    if wakes:
        out["stream_chunks_per_wake"] = chunks / wakes
    return out
