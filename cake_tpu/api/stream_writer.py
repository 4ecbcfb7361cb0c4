"""One thread writes every streamed chunk of an API server.

The engine thread emits a token a row a step. Handing each to its own
handler thread cost a wake-up and a hand-over of the interpreter lock a
token: the woken handler built its chunk and wrote it while the engine
thread, mid-loop, waited for the lock (PERF.md §6, PR 50 and PR 54). Here
the engine's callback (`ChatStream.feed`) only appends; the engine signals
the writer once where an `emit` span closes (obs/steps.StepTelemetry.
stream_wake), and the writer turns the step's deltas into chunks and sends
each in one `send`, while the engine thread waits on the device.

A stream has one owner at a time. Its handler thread parses, admits,
writes the headers and the attach replay, then hands the stream over
(`StreamWriter.write`) and waits on the stream's one event; deltas emitted
before that wait in the stream's own deque. The writer gives the stream
back when it wrote the final delta, when the request ended without one
(the error path), when the client went away, when its own code raised,
and when the socket would block: a slow client's stream goes on on its
handler thread, which can wait (`ChatStream.pump`), and holds up neither
the engine loop nor the other streams. The writer never touches a socket
before the hand-over or after the hand-back.
"""

from __future__ import annotations

import json
import logging
import socket
import threading
import time
import uuid
from collections import deque
from typing import Optional

from cake_tpu.api.openai import chunk_response
from cake_tpu.obs import metrics as obs_metrics

log = logging.getLogger(__name__)

# how a stream came back from the writer (StreamWriter.write), or ended
# on its handler thread (ChatStream.pump)
FINAL = "final"        # the final delta is written
ENDED = "ended"        # the request ended without one (the error path)
GONE = "gone"          # the client went away
BLOCKED = "blocked"    # the socket would block: `unsent` is what is left
FAILED = "failed"      # the writer's own code raised: `error`

# no chunk depends on the engine's signal alone: with a stream in hand
# the writer also looks every TICK_S
TICK_S = 0.02
# how often it looks for requests that ended without a final delta
SWEEP_S = 0.25

_HANDED_BACK = obs_metrics.counter(
    "cake_stream_handed_back_total",
    "Streams the stream writer gave back to their handler threads "
    "because the socket would block (a client that reads slowly)")


def sse_chunk(obj: dict, event_id=None) -> bytes:
    """One server-sent event as one chunk of a chunked response: the
    size line, the payload and the trailing CRLF in one piece, for one
    write. The `id:` field makes the stream resumable: it is the
    absolute token position the event covers up to, and a reconnect
    echoes it back as Last-Event-ID."""
    head = f"id: {int(event_id)}\n" if event_id is not None else ""
    payload = f"{head}data: {json.dumps(obj)}\n\n".encode()
    return b"%x\r\n%s\r\n" % (len(payload), payload)


def lp_entry(tokenizer, n_top: int, t: int, lp: float, top) -> dict:
    """One token's `logprobs.content` entry."""
    text = tokenizer.decode([t])
    e = {"token": text, "logprob": round(lp, 6),
         "bytes": list(text.encode()), "top_logprobs": []}
    if n_top:
        def alt(at, al):
            atext = tokenizer.decode([at])
            return {"token": atext, "logprob": round(al, 6),
                    "bytes": list(atext.encode())}
        e["top_logprobs"] = [alt(at, al) for at, al in top[:n_top]]
    return e


class ChatStream:
    """One streaming request's state between the engine's deltas and the
    chunks on the wire: what the client holds (`sent_id`), which logprob
    entries have shipped (`lp_cursor`), and where a resumed client's
    Last-Event-ID lies (`trim_from`). Whoever owns the stream (its
    handler thread or the writer) calls `chunk`; the engine thread calls
    `feed` alone."""

    def __init__(self, tokenizer, eos_ids, model_name: str, want_lp: bool,
                 n_top: int, writer: Optional["StreamWriter"] = None):
        self.rid = str(uuid.uuid4())
        self.tokenizer = tokenizer
        self.eos_ids = eos_ids
        self.model_name = model_name
        self.want_lp = want_lp
        self.n_top = n_top
        # (delta, final, n_done) as the engine emitted them
        self.items: deque = deque()
        # set for the handler thread: a delta (when it owns the stream)
        # or the writer's hand-back
        self.wake = threading.Event()
        # the writer's list of streams with new deltas, while the writer
        # has or will have this stream; None on a handler's own stream
        self._dirty = writer.dirty if writer is not None else None
        self.req = None
        self.final = False
        self.outcome: Optional[str] = None
        self.unsent = b""
        self.error: Optional[BaseException] = None

    def feed(self, delta: str, final: bool, n_done: int = 0) -> bool:
        """The engine's stream callback (engine thread): an append, no
        lock and, for a stream the writer has, no wake-up. True when the
        delta waits for the writer."""
        self.items.append((delta, final, n_done))
        dirty = self._dirty
        if dirty is not None:
            dirty.append(self)
            return True
        self.wake.set()
        return False

    # wants_count: the engine snapshots the finalized-entry count on the
    # engine thread at emit time, so each chunk's logprob entries pair
    # exactly with the delta carrying their text (a held-back UTF-8 tail
    # token's entry ships with the later chunk that contains its text,
    # never ahead of it)
    feed.wants_count = True

    def bind(self, req) -> None:
        """The admitted (or attached) request. SSE event ids are
        ABSOLUTE token positions: tokens replayed from previous process
        generations count, so a client's Last-Event-ID survives any
        number of restarts."""
        self.req = req
        self.id_base = len(getattr(req, "replayed_tokens", ()) or ())
        self.sent_id = self.id_base   # high-water mark of delivered ids
        self.lp_cursor = 0
        self.trim_from = None

    def replay(self, last_event_id):
        """An idempotent reconnect: the held/journaled suffix after the
        client's Last-Event-ID as ONE chunk (its id is the absolute
        position it covers up to), or None where nothing is missing.
        Deltas at or below the replayed high-water mark are dropped by
        `chunk`, so the client sees exactly the missing tokens: no
        duplicates, no gaps."""
        r = self.req
        history = (list(getattr(r, "replayed_tokens", ()) or ())
                   + list(r.out_tokens))
        start_at = max(0, int(last_event_id or 0))
        suffix = [t for t in history[start_at:] if t not in self.eos_ids]
        self.sent_id = max(start_at, len(history))
        self.lp_cursor = max(0, self.sent_id - self.id_base)
        if not suffix:
            return None
        return (chunk_response(self.tokenizer.decode(suffix),
                               self.model_name, rid=self.rid),
                len(history))

    def resume_after(self, last_event_id: int) -> None:
        """A FRESH admission that arrives with a Last-Event-ID (the
        front-door router failing a keyed stream over to a different
        replica, which re-runs the whole prompt deterministically):
        events at or below the client's high-water mark are suppressed,
        and the first batch crossing it re-decodes only the unseen token
        suffix — the attach path's exact-suffix semantics, without a
        local attach to replay from. Same text re-decode boundary caveat
        as the attach replay."""
        self.sent_id = max(self.sent_id, int(last_event_id))
        self.lp_cursor = max(0, self.sent_id - self.id_base)
        self.trim_from = self.lp_cursor

    def _chunk_lp(self, upto: int):
        if not self.want_lp:
            return None
        r = self.req
        entries = [
            lp_entry(self.tokenizer, self.n_top, r.out_tokens[i],
                     r.out_logprobs[i], r.out_top[i])
            for i in range(self.lp_cursor, upto)
            if r.out_tokens[i] not in self.eos_ids
        ]
        self.lp_cursor = upto
        return entries

    def chunk(self, delta: str, n_done: int):
        """One delta as (chunk object, event id), or None where nothing
        goes on the wire: an empty delta, one the client holds already,
        a crossing batch that was EOS alone (the position advances)."""
        ev_id = self.id_base + n_done
        if not delta or ev_id <= self.sent_id:
            return None
        if self.trim_from is not None:
            # the batch crossing the resumed client's Last-Event-ID:
            # ship only the unseen suffix
            toks = [t for t in self.req.out_tokens[self.trim_from:n_done]
                    if t not in self.eos_ids]
            delta = self.tokenizer.decode(toks) if toks else ""
            self.trim_from = None
        self.sent_id = ev_id
        if not delta:
            return None
        return (chunk_response(delta, self.model_name, rid=self.rid,
                               logprobs=self._chunk_lp(n_done)),
                ev_id)

    def finish_chunk(self):
        """The `finish="stop"` chunk: it flushes entries finalized after
        the last text-bearing delta (e.g. an EOS-terminated request whose
        final delta was empty), keeping the one-entry-per-token contract;
        the request is done, so the full lists are stable."""
        n = len(self.req.out_tokens)
        return (chunk_response("", self.model_name, finish="stop",
                               rid=self.rid, logprobs=self._chunk_lp(n)),
                self.id_base + n)

    def pump(self, send, sock=None) -> str:
        """The stream on its handler thread, to its end: blocking writes
        (`send(obj, event_id)`), a wake-up a delta. What the writer left
        unsent of a chunk goes first, to `sock`."""
        try:
            if self.unsent:
                sock.sendall(self.unsent)
                self.unsent = b""
            while not self.final:
                # the request's end is read BEFORE the deque: its last
                # delta was appended before `done` was set
                ended = self.req.done.is_set()
                while self.items and not self.final:
                    delta, self.final, n_done = self.items.popleft()
                    out = self.chunk(delta, n_done)
                    if out is not None:
                        send(*out)
                if self.final:
                    break
                if ended:
                    return ENDED
                self.wake.wait(0.5)
                self.wake.clear()
        except OSError:
            return GONE
        return FINAL


class StreamWriter:
    """The thread that writes the streams handed to it, and its inbox.
    Started by the first hand-over; `close()` joins it."""

    def __init__(self):
        # streams the engine appended a delta to (engine thread), and
        # (stream, socket) hand-overs (handler threads): deques, so an
        # append takes no lock
        self.dirty: deque = deque()
        self._handed: deque = deque()
        self._wake = threading.Event()
        self._streams: dict = {}      # stream -> its socket, while held
        self._swept = 0.0
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        self._start_lock = threading.Lock()

    def signal(self) -> None:
        """Deltas wait (the engine thread, once where an `emit` span
        closes)."""
        self._wake.set()

    def write(self, stream: ChatStream, sock) -> str:
        """Hand `stream` and its socket over and wait until the writer
        gives them back (a handler thread). How it ended: FINAL, ENDED,
        GONE, BLOCKED (`stream.unsent`) or FAILED (`stream.error`)."""
        with self._start_lock:
            if self._closed:
                return self._release(stream)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="cake-stream-writer",
                    daemon=True)
                self._thread.start()
            self._handed.append((stream, sock))
        self._wake.set()
        stream.wake.wait()
        stream.wake.clear()
        return stream.outcome

    def close(self) -> None:
        """Give every stream back to its handler thread and join."""
        with self._start_lock:
            self._closed = True
            thread = self._thread
        self._wake.set()
        if thread is not None:
            thread.join(10.0)

    # -- the writer thread ---------------------------------------------------

    @staticmethod
    def _release(stream: ChatStream, outcome: str = BLOCKED) -> str:
        """The stream is its handler thread's from here on."""
        stream._dirty = None
        stream.outcome = outcome
        stream.wake.set()
        return outcome

    def _give_back(self, stream: ChatStream, outcome: str) -> None:
        del self._streams[stream]
        self._release(stream, outcome)

    def _run(self) -> None:
        while not self._closed:
            self._wake.wait(TICK_S if self._streams else None)
            self._wake.clear()
            try:
                self._turn()
            except Exception as e:  # noqa: BLE001 — the thread must live
                log.exception("stream writer failed; ending its streams")
                for stream in list(self._streams):
                    stream.error = e
                    self._give_back(stream, FAILED)
        for stream, _sock in self._handed:
            self._release(stream)
        for stream in list(self._streams):
            self._give_back(stream, BLOCKED)

    def _turn(self) -> None:
        while self._handed:
            stream, sock = self._handed.popleft()
            self._streams[stream] = sock
            self._drain(stream)      # what waited for the hand-over
        while self.dirty:
            stream = self.dirty.popleft()
            if stream in self._streams:
                self._drain(stream)
            elif stream._dirty is None:
                # given back since the engine appended: its handler
                # thread drains it
                stream.wake.set()
        now = time.monotonic()
        if now - self._swept >= SWEEP_S:
            # requests that ended without a final delta (the error path)
            self._swept = now
            for stream in list(self._streams):
                self._drain(stream)

    def _drain(self, stream: ChatStream) -> None:
        sock, items = self._streams[stream], stream.items
        try:
            # the request's end is read BEFORE the deque, as in pump
            ended = stream.req.done.is_set()
            while items:
                delta, stream.final, n_done = items.popleft()
                out = stream.chunk(delta, n_done)
                if out is not None:
                    data = sse_chunk(*out)
                    try:
                        sent = sock.send(data, socket.MSG_DONTWAIT)
                    except BlockingIOError:
                        sent = 0
                    if sent < len(data):
                        stream.unsent = data[sent:]
                        _HANDED_BACK.inc()
                        return self._give_back(stream, BLOCKED)
                if stream.final:
                    return self._give_back(stream, FINAL)
            if ended:
                self._give_back(stream, ENDED)
        except OSError:
            self._give_back(stream, GONE)
        except Exception as e:  # noqa: BLE001 — one stream's fault
            log.exception("stream writer: a stream failed")
            stream.error = e
            self._give_back(stream, FAILED)
