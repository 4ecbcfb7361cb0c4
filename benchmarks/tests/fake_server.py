"""A stand-in for the served path with a known service time: every
request waits `first_s`, then streams `max_tokens` one-token chunks
`gap_s` apart, in the program's SSE shape."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


def start(first_s: float, gap_s: float):
    state = {"rid": 0, "bodies": [], "lock": threading.Lock()}

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.0"

        def log_message(self, *a):
            pass

        def do_POST(self):
            body = json.loads(self.rfile.read(
                int(self.headers["Content-Length"])))
            with state["lock"]:
                state["rid"] += 1
                rid = state["rid"]
                state["bodies"].append(body)
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("x-cake-rid", str(rid))
            self.end_headers()
            time.sleep(first_s)
            try:
                for i in range(body["max_tokens"]):
                    if i:
                        time.sleep(gap_s)
                    entry = {"token": "w1", "logprob": -1.0,
                             "top_logprobs": [{"logprob": -1.0 - k}
                                              for k in range(5)]}
                    chunk = {"choices": [{"delta": {"content": " w1"},
                                          "logprobs": {"content": [entry]}}]}
                    self.wfile.write(
                        f"id: {i + 1}\ndata: {json.dumps(chunk)}\n\n".encode())
                    self.wfile.flush()
                self.wfile.write(b"data: [DONE]\n\n")
            except OSError:
                pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, state
