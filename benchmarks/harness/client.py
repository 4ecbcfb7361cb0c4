"""One streamed chat completion, timed from the client's side.

Standard library only. A request's record holds the host-clock time of
every token as it arrived (`time.monotonic()`), the token's logprob, and
for a probe the top-5 of the first token. A request that returns
non-200, breaks mid-stream or ends short of the asked length is
`failed` and has no latency.
"""

from __future__ import annotations

import http.client
import json
import math
import time

REQUEST_TIMEOUT_S = 900


def chat_body(content: str, max_tokens: int, top_logprobs: int = 0) -> dict:
    body = {"messages": [{"role": "user", "content": content}],
            "max_tokens": max_tokens, "temperature": 0.0,
            "logprobs": True, "stream": True}
    if top_logprobs:
        body["top_logprobs"] = top_logprobs
    return body


def stream_chat(port: int, item: dict, stop=None,
                timeout: float = REQUEST_TIMEOUT_S) -> dict:
    """Send `item` ({"content", "out", "top_logprobs"?, ...}) and read
    the stream to its end, or until `stop` is set (then the record is
    `cut`: what arrived counts, the request is neither finished nor
    failed). Returns the item's fields plus the timings."""
    rec = dict(item)
    rec.pop("content", None)
    rec.update(status=0, rid=None, token_t=[], logprobs=[], first_top=None,
               finished=False, failed=False, cut=False, error=None)
    body = json.dumps(chat_body(item["content"], item["out"],
                                item.get("top_logprobs", 0))).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    rec["t_send"] = time.monotonic()
    try:
        conn.request("POST", "/api/v1/chat/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        rid = resp.getheader("x-cake-rid")
        rec["rid"] = int(rid) if rid is not None else None
        if resp.status != 200:
            rec["error"] = resp.read()[:300].decode(errors="replace")
            rec["failed"] = True
            return rec
        done = False
        while not done:
            if stop is not None and stop.is_set():
                rec["cut"] = True
                break
            line = resp.readline()
            if not line:
                break
            now = time.monotonic()
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            if line == b"data: [DONE]":
                done = True
                break
            event = json.loads(line[len(b"data: "):])
            if "error" in event:
                rec["error"] = json.dumps(event["error"])[:300]
                break
            choice = event["choices"][0]
            entries = (choice.get("logprobs") or {}).get("content") or []
            for e in entries:
                if rec["first_top"] is None:
                    rec["first_top"] = [t["logprob"] for t in
                                        e.get("top_logprobs", [])]
                rec["token_t"].append(now)
                rec["logprobs"].append(e["logprob"])
        rec["t_end"] = time.monotonic()
        if rec["cut"]:
            return rec
        n = len(rec["token_t"])
        if done and n == item["out"] and all(
                math.isfinite(lp) for lp in rec["logprobs"]):
            rec["finished"] = True
        else:
            rec["failed"] = True
            rec["error"] = rec["error"] or (
                f"stream ended with {n} of {item['out']} tokens"
                + ("" if done else ", before [DONE]"))
        return rec
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["t_end"] = time.monotonic()
        if stop is not None and stop.is_set():
            rec["cut"] = True
        else:
            rec["failed"] = True
            rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    finally:
        conn.close()
