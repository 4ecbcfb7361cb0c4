"""The API server's stream writer (api/stream_writer.py, PR 54): one
thread writes every streamed chunk, woken once a step. Counts and bytes,
never times: what a client receives through the writer is what the
handler path sends, a slow client holds up nobody, and a stream always
ends."""

import gc
import json
import re
import socket
import struct
import sys
import threading
import time
import urllib.request
import weakref
from http.server import ThreadingHTTPServer

import pytest

import jax
import jax.numpy as jnp

from cake_tpu.api import stream_writer as sw
from cake_tpu.api.server import ApiServer, make_handler, start
from cake_tpu.args import Args
from cake_tpu.master import Master
from cake_tpu.models.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator
from cake_tpu.models.llama.params import init_params
from cake_tpu.ops.sampling import SamplingConfig

PATH = "/api/v1/chat/completions"
SLOTS = 4


class LetterTokenizer(ByteTokenizer):
    """Every id is a letter, so every token is a chunk (random weights
    under the byte tokenizer mostly decode to nothing)."""

    def decode(self, ids):
        return "".join(chr(97 + i % 26) for i in ids if i >= self.OFFSET)


def build(eos=(2,), seq=256, **args):
    cfg = LlamaConfig.tiny(num_hidden_layers=2, eos_token_ids=tuple(eos))
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    gen = LlamaGenerator(cfg, params, LetterTokenizer(cfg.vocab_size),
                         max_seq_len=seq,
                         sampling=SamplingConfig(temperature=0.0,
                                                 repeat_penalty=1.0),
                         cache_dtype=jnp.float32)
    master = Master(Args(sample_len=8, kv_pages=seq // 16 * SLOTS + 8,
                         kv_page_size=16, **args), text_generator=gen)
    return master, master.make_engine(max_slots=SLOTS)


class Served:
    """A tiny paged engine behind the real HTTP server."""

    def __init__(self, sndbuf=None, **kw):
        self.master, self.engine = build(**kw)
        self.api = ApiServer(self.master, "m", engine=self.engine)
        handler = make_handler(self.api)
        if sndbuf:
            # the first connection's socket holds a few chunks and no more
            first = [sndbuf]

            class Handler(handler):
                def setup(self):
                    if first:
                        self.request.setsockopt(socket.SOL_SOCKET,
                                                socket.SO_SNDBUF, first.pop())
                    super().setup()
            handler = Handler
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.port = self.httpd.server_address[1]

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.engine.stop(timeout=10)
        self.api.stream_writer.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def connect(self, body, headers=None, rcvbuf=None):
        return connect(self.port, body, headers, rcvbuf)

    def post(self, body, headers=None):
        """(header bytes, body bytes) of one whole streamed response."""
        with self.connect(body, headers) as s:
            return read_all(s)


def connect(port, body, headers=None, rcvbuf=None):
    """A client that sees bytes: the request is sent, nothing read."""
    s = socket.socket()
    if rcvbuf:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.settimeout(60)
    s.connect(("127.0.0.1", port))
    data = json.dumps(dict(body, stream=True)).encode()
    head = {"Host": "t", "Content-Type": "application/json",
            "Content-Length": str(len(data)), "Connection": "close",
            **(headers or {})}
    s.sendall((f"POST {PATH} HTTP/1.1\r\n"
               + "".join(f"{k}: {v}\r\n" for k, v in head.items())
               + "\r\n").encode() + data)
    return s


def read_all(s):
    buf = b""
    while True:
        got = s.recv(65536)
        if not got:
            break
        buf += got
    head, _, body = buf.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200"), head
    return head, body


def normal(body: bytes) -> bytes:
    """The body less what differs between two requests for the same
    tokens: the response's uuid and its second. Both keep their length,
    so the chunk-size lines stay comparable."""
    body = re.sub(rb'"id": "[0-9a-f-]{36}"',
                  b'"id": "' + b"0" * 36 + b'"', body)
    return re.sub(rb'"created": \d+',
                  lambda m: b'"created": ' + b"1" * (len(m[0]) - 11), body)


def events(body: bytes):
    """[(event id or None, object or "[DONE]")] of a chunked SSE body,
    held to its framing: each chunk's size line counts its payload."""
    out, rest = [], body
    while rest:
        size, _, rest = rest.partition(b"\r\n")
        n = int(size, 16)
        payload, rest = rest[:n], rest[n + 2:]
        if n == 0:
            break
        ev_id = None
        for line in payload.decode().strip().split("\n"):
            if line.startswith("id: "):
                ev_id = int(line[4:])
            elif line.startswith("data: "):
                data = line[6:]
                out.append((ev_id, data if data == "[DONE]"
                            else json.loads(data)))
    assert not rest
    return out


def user(text="hi"):
    return {"messages": [{"role": "user", "content": text}]}


def wait_for(cond, seconds=30.0):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.01)


@pytest.fixture(scope="module")
def served():
    with Served() as s:
        yield s


def handler_path(api):
    """The same server with its streams on their handler threads: a
    closed writer hands every stream back before it touches it."""
    closed = sw.StreamWriter()
    closed.close()
    api.stream_writer, was = closed, api.stream_writer
    return was


# -- the bytes ------------------------------------------------------------


def fourth_token(served):
    """The token greedy decoding emits fourth for `user()`."""
    body = events(served.post(dict(user(), max_tokens=6, logprobs=True))[1])
    toks = [e["token"] for _i, obj in body if obj != "[DONE]"
            for e in (obj["choices"][0]["logprobs"] or {"content": []}
                      )["content"]]
    assert len(toks) == 6
    return toks[3]


CASES = {
    "plain": (dict(max_tokens=6), {}),
    "logprobs": (dict(max_tokens=6, logprobs=True), {}),
    "top_logprobs": (dict(max_tokens=6, logprobs=True, top_logprobs=3), {}),
    "eos_final": (dict(max_tokens=6, logprobs=True), {}),
    "attach": (dict(max_tokens=6, logprobs=True),
               {"x-cake-idempotency-key": "K", "Last-Event-ID": "2"}),
    "fresh_crossing": (dict(max_tokens=6, logprobs=True),
                       {"x-cake-idempotency-key": "K",
                        "Last-Event-ID": "3"}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_writer_sends_the_handler_paths_bytes(served, case):
    """One chunk a token, the chunk's JSON, the `id:` line, the entries
    paired with their delta: byte for byte what the handler thread
    writes, in every shape a stream takes."""
    extra, headers = CASES[case]
    body = dict(user(), **extra)
    ctx = served
    if case == "eos_final":
        # a model whose fourth token ends the output: the final delta
        # carries no text and goes on no wire
        letter = fourth_token(served)
        eos = [i for i in range(3, 256)
               if LetterTokenizer().decode([i]) == letter]
        ctx = Served(eos=eos)
    try:
        sides = []
        for side in ("writer", "handler"):
            hdrs = {k: v.replace("K", f"{case}-{side}")
                    for k, v in headers.items()}
            if case == "attach":
                # the stream the reconnect attaches to, run to its end
                ctx.post(body, {k: v for k, v in hdrs.items()
                                if k != "Last-Event-ID"})
            _head, got = ctx.post(body, hdrs)
            sides.append(normal(got))
            if side == "writer":
                was = handler_path(ctx.api)
        ctx.api.stream_writer = was
        through_writer, through_handler = sides
        assert through_writer == through_handler
        evs = events(through_writer)
        assert evs[-1] == (None, "[DONE]")
        assert evs[-2][1]["choices"][0]["finish_reason"] == "stop"
        ids = [i for i, _obj in evs[:-1]]
        assert ids == sorted(ids)
        texts = [obj["choices"][0]["delta"].get("content")
                 for _i, obj in evs[:-2]]
        if case in ("plain", "logprobs", "top_logprobs"):
            assert ids == [1, 2, 3, 4, 5, 6, 6]
            assert all(len(t) == 1 for t in texts)
        elif case == "eos_final":
            assert ids == [1, 2, 3, 4]       # three letters, then stop
        elif case == "attach":
            assert ids == [6, 6] and len(texts[0]) == 4
        else:
            assert ids == [4, 5, 6, 6]
        if extra.get("logprobs") and case != "attach":
            for _i, obj in evs[:-2]:
                entries = obj["choices"][0]["logprobs"]["content"]
                assert ("".join(e["token"] for e in entries)
                        == obj["choices"][0]["delta"]["content"])
                assert all(len(e["top_logprobs"])
                           == extra.get("top_logprobs", 0) for e in entries)
    finally:
        if ctx is not served:
            ctx.close()


# -- ownership --------------------------------------------------------------


def test_deltas_before_the_hand_over_follow_the_headers_in_order(served,
                                                                 monkeypatch):
    """What the engine emits while the handler thread still has the
    socket waits in the stream's own deque."""
    waited = []
    write = served.api.stream_writer.write

    def late(stream, sock):
        wait_for(lambda: stream.req.done.is_set())
        waited.append(len(stream.items))
        return write(stream, sock)

    monkeypatch.setattr(served.api.stream_writer, "write", late)
    head, body = served.post(dict(user(), max_tokens=6))
    assert waited == [6]
    assert b"text/event-stream" in head
    assert [i for i, _o in events(body)[:-1]] == [1, 2, 3, 4, 5, 6, 6]


def test_a_slow_client_is_handed_back_and_holds_up_nobody():
    """A client that stops reading fills its socket: the writer gives
    that stream to its handler thread, which can wait, and the other
    streams' chunks keep arriving."""
    n = 160
    body = dict(user(), max_tokens=n, logprobs=True, top_logprobs=5)
    with Served(sndbuf=4096, seq=512) as ctx:
        before = sw._HANDED_BACK.value
        slow = ctx.connect(body, rcvbuf=4096)
        # it reads its headers and no more, until the others are done
        wait_for(lambda: sw._HANDED_BACK.value > before)
        for _ in range(2):
            evs = events(ctx.post(body)[1])
            assert [i for i, _o in evs[:-1]] == list(range(1, n + 1)) + [n]
        assert sw._HANDED_BACK.value == before + 1
        evs = events(read_all(slow)[1])
        slow.close()
        assert [i for i, _o in evs[:-1]] == list(range(1, n + 1)) + [n]
        assert evs[-1] == (None, "[DONE]")


@pytest.mark.parametrize("keyed", [False, True])
def test_a_disconnect_cancels_unless_idempotency_keyed(served, monkeypatch,
                                                       keyed):
    cancelled = []
    cancel = served.engine.cancel
    monkeypatch.setattr(served.engine, "cancel",
                        lambda h: (cancelled.append(h), cancel(h)))
    done = served.engine.stats.requests_completed
    headers = {"x-cake-idempotency-key": "gone"} if keyed else {}
    s = served.connect(dict(user(), max_tokens=120), headers)
    assert s.recv(64)
    # a reset, not a FIN: the next send fails
    s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                 struct.pack("ii", 1, 0))
    s.close()
    if keyed:
        wait_for(lambda: served.engine.stats.requests_completed > done)
        assert not cancelled
    else:
        wait_for(lambda: cancelled)
        wait_for(lambda: served.engine.active == 0)
        assert served.engine.stats.requests_completed == done


def test_the_writers_own_exception_ends_the_stream_with_an_error_event(
        served, monkeypatch):
    chunk = sw.ChatStream.chunk

    def broken(self, delta, n_done):
        if n_done == 3:
            raise RuntimeError("no chunk for you")
        return chunk(self, delta, n_done)

    monkeypatch.setattr(sw.ChatStream, "chunk", broken)
    evs = events(served.post(dict(user(), max_tokens=6))[1])
    assert [i for i, _o in evs[:2]] == [1, 2]
    assert evs[2][1]["error"] == {"message": "no chunk for you",
                                  "type": "RuntimeError",
                                  "retryable": False}
    assert evs[3:] == [(None, "[DONE]")]
    monkeypatch.undo()
    # and the writer lives: the next stream is whole
    assert len(events(served.post(dict(user(), max_tokens=6))[1])) == 8


def test_a_finished_request_goes_without_a_collection(served, monkeypatch):
    """The request holds its stream (the callback) and the stream the
    request: the server lets go when the stream ends, so a finished
    request's token lists do not wait for a full collection (on the
    chip they made it half again as long: PERF.md section 6, PR 54)."""
    refs = []
    chat = served.engine.chat

    def spy(*a, **kw):
        h = chat(*a, **kw)
        refs.append(weakref.ref(h._req))
        return h

    monkeypatch.setattr(served.engine, "chat", spy)
    gc.collect()
    gc.disable()
    try:
        for _ in range(2):
            served.post(dict(user(), max_tokens=6, logprobs=True))
        wait_for(lambda: not any(r() for r in refs))
    finally:
        gc.enable()


def test_shutdown_joins_the_writer_thread():
    def writers():
        return [t for t in threading.enumerate()
                if t.name == "cake-stream-writer" and t.is_alive()]

    before = len(writers())
    master, engine = build()
    httpd = start(master, address="127.0.0.1:0", block=False,
                  engine=engine)
    with connect(httpd.server_address[1], dict(user(), max_tokens=4)) as s:
        assert len(events(read_all(s)[1])) == 6
    assert len(writers()) == before + 1
    httpd.shutdown()
    wait_for(lambda: len(writers()) == before)
    httpd.server_close()


# -- one wake a step ----------------------------------------------------------


def test_one_wake_a_step_for_all_its_rows():
    """The engine signals the writer where an `emit` span closes, once
    for the step's rows; a per-token callback is counted apart."""
    n = 24
    with Served() as ctx:
        wakes = []
        signal = ctx.engine.flight.stream_wake
        ctx.engine.flight.stream_wake = lambda: (wakes.append(1), signal())
        got = []
        threads = [threading.Thread(target=lambda: got.append(
            events(ctx.post(dict(user(), max_tokens=n))[1])))
            for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert len(got) == 3 and all(len(e) == n + 2 for e in got)
        # a plain callable is called a token, as ever
        seen = []
        ctx.engine.chat([Message.user("hi")],
                        max_new_tokens=4,
                        stream=lambda d, f: seen.append(d)).wait()
        assert len(seen) == 4
        # one more step, so that the last emit's counts are in a record
        ctx.engine.chat([Message.user("hi")],
                        max_new_tokens=2).wait()
        recs = ctx.engine.flight.dump()
        chunks = sum(r.get("stream_chunks", 0) for r in recs)
        woken = sum(r.get("stream_wakes", 0) for r in recs)
        assert chunks == 3 * n and len(wakes) == woken
        assert all(r.get("stream_wakes", 0) <= 1 for r in recs)
        assert all(r.get("stream_chunks", 0) <= 3 for r in recs)
        # the three rows of a step behind ONE wake
        assert any(r.get("stream_chunks") == 3 and r["stream_wakes"] == 1
                   for r in recs)
        assert n <= woken < 3 * n / 2
        assert sum(r.get("stream_direct", 0) for r in recs) == 4
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{ctx.port}/metrics", timeout=30).read().decode()
        for series in ('cake_stream_chunks_total{path="writer"}',
                       'cake_stream_chunks_total{path="handler"}',
                       "cake_stream_writer_wakes_total",
                       "cake_stream_handed_back_total"):
            assert series in text


def test_many_streams_under_a_short_switch_interval():
    """More streams than slots and than cores' worth of threads, the
    interpreter handing over every few microseconds: every stream is
    whole and in order, whoever wrote it."""
    n, clients = 12, 10
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with Served() as ctx:
            got, errors = [], []

            def one(k):
                try:
                    for _ in range(2):
                        got.append(events(ctx.post(
                            dict(user(f"hi {k}"), max_tokens=n))[1]))
                except Exception as e:  # noqa: BLE001 — shown below
                    errors.append(e)

            threads = [threading.Thread(target=one, args=(k,))
                       for k in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not errors and not any(t.is_alive() for t in threads)
            assert len(got) == 2 * clients
            for evs in got:
                assert [i for i, _o in evs[:-1]] \
                    == list(range(1, n + 1)) + [n]
                assert evs[-1] == (None, "[DONE]")
    finally:
        sys.setswitchinterval(was)
