"""One general generator for every traffic mix, read from a data file.

A mix is a FIXED cycle of requests: every run of a cell sends the same
prompt and output lengths in the same order (the file's `order_seed`
shuffles the multiset once), and --seed chooses the words of each
prompt. So two runs differ in what a run cannot help (the host's
timing) and in nothing else.

    {"loop": "closed", "clients": 16, "ramp_s": 8,
     "prompt_classes": [{"name": "w1", "lo": 65, "hi": 128, "weight": 0.3}],
     "multiset": [{"class": "w1", "out": 48, "n": 6}, ...],
     "probe": {"class": "w1", "out": 48},
     "warmup": [{"class": "w1", "out": 8}, ...], "warmup_wave": 16}

An open loop has `"loop": "open"` and `"rate_rps"` (with optional
`"burst"`: {"every_s", "size"}) instead of `clients`: the schedule is
deterministic, requests are sent when due whatever is still in flight,
and latency counts from the due time.
"""

from __future__ import annotations

import random
import threading
import time

from .client import stream_chat
from .spec import SpecError

PROBE_SEED = 20260927      # the probe's words never depend on --seed


def class_by_name(traffic: dict) -> dict:
    return {c["name"]: c for c in traffic["prompt_classes"]}


def expand_multiset(traffic: dict) -> list:
    """The cycle's items in file order: [{"class", "out", "prompt"}].
    The k-th item of a class of n gets the k-th of n prompt lengths
    spread evenly over [lo, hi]; the class weights must be the
    multiset's own proportions."""
    classes = class_by_name(traffic)
    items, per_class = [], {}
    for row in traffic["multiset"]:
        if row["class"] not in classes:
            raise SpecError(f"multiset names class {row['class']!r}, "
                            "which prompt_classes does not have")
        for _ in range(int(row["n"])):
            items.append({"class": row["class"], "out": int(row["out"])})
            per_class[row["class"]] = per_class.get(row["class"], 0) + 1
    seen = {}
    for it in items:
        c = classes[it["class"]]
        n, k = per_class[it["class"]], seen.get(it["class"], 0)
        seen[it["class"]] = k + 1
        span = c["hi"] - c["lo"]
        it["prompt"] = c["lo"] + (span * k // (n - 1) if n > 1 else span)
    total = len(items)
    for name, c in classes.items():
        share = per_class.get(name, 0) / total
        if abs(share - c["weight"]) > 1e-9:
            raise SpecError(
                f"class {name!r} has weight {c['weight']} but "
                f"{per_class.get(name, 0)} of the multiset's {total} items")
    return items


def words(rng: random.Random, n: int, vocab_size: int) -> str:
    """n words of the generated tokenizer's vocabulary: n tokens."""
    return " ".join(f"w{rng.randrange(1, vocab_size)}" for _ in range(n))


class Mix:
    """A traffic file made runnable: the cycle in its fixed order, and
    each request's seeded content on demand."""

    def __init__(self, traffic: dict, seed: int, vocab_size: int,
                 overhead: int = 0):
        self.traffic = traffic
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.overhead = int(overhead)   # the chat template's own tokens
        self.items = expand_multiset(traffic)
        order = list(range(len(self.items)))
        # the ORDER is the traffic file's (`order_seed`), the same in
        # every run: which prefills share a mixed step follows from the
        # order, and a cycle permuted by --seed moved out_tok_s by 5 %
        # from seed to seed on the chip. --seed fills the words.
        random.Random(int(traffic.get("order_seed", 0))).shuffle(order)
        probe = traffic.get("probe")
        self.probe_index = None
        if probe:
            for i in order:
                it = self.items[i]
                if (it["class"], it["out"]) == (probe["class"],
                                                int(probe["out"])):
                    self.probe_index = i
                    break
            if self.probe_index is None:
                raise SpecError("the probe's (class, out) is not an item "
                                "of the multiset")
            # the probe goes just behind the first wave, whatever the
            # seed: it is then always served amid traffic, early in the
            # ramp, and again one cycle later
            at = min(int(traffic.get("clients", 0)) + 2, len(order) - 1)
            order.remove(self.probe_index)
            order.insert(at, self.probe_index)
        self.order = order

    def probe_item(self) -> dict:
        it = dict(self.items[self.probe_index])
        it.update(content=words(random.Random(PROBE_SEED),
                                it["prompt"] - self.overhead,
                                self.vocab_size),
                  top_logprobs=5, probe=True, n=-1)
        return it

    def item(self, n: int) -> dict:
        """The n-th request of the run (n = 0, 1, ...)."""
        i = self.order[n % len(self.order)]
        if i == self.probe_index:
            it = self.probe_item()
        else:
            it = dict(self.items[i])
            # a 64-bit mix of (seed, n): distinct words for every
            # request of every cycle, so no two prompts share a prefix
            rng = random.Random((self.seed << 24) ^ (n * 2654435761))
            it["content"] = words(rng, it["prompt"] - self.overhead,
                                  self.vocab_size)
        it["n"] = n
        return it

    def warmup_items(self) -> list:
        classes = class_by_name(self.traffic)
        out = []
        for j, w in enumerate(self.traffic.get("warmup", [])):
            rng = random.Random(PROBE_SEED + 1 + j)
            prompt = classes[w["class"]]["hi"]
            out.append({"class": w["class"], "out": int(w["out"]),
                        "prompt": prompt, "n": -2 - j,
                        "content": words(rng, prompt - self.overhead,
                                         self.vocab_size)})
        return out


class ClosedLoop:
    """`clients` threads; each takes the cycle's next item when its last
    request has ended, with no think time. Records every request and,
    per client, the turnaround from one request's end to the next send
    (a starved generator shows there)."""

    def __init__(self, port: int, mix: Mix, clients: int):
        self.port, self.mix, self.clients = port, mix, clients
        self.records, self.turnarounds = [], []
        self._lock = threading.Lock()
        self._next = 0
        self.stop_event = threading.Event()
        self._threads = []

    def _take(self) -> int:
        with self._lock:
            n = self._next
            self._next += 1
            return n

    def _client(self) -> None:
        last_end = None
        while not self.stop_event.is_set():
            item = self.mix.item(self._take())
            if last_end is not None:
                self.turnarounds.append(
                    (time.monotonic(), time.monotonic() - last_end))
            rec = stream_chat(self.port, item, stop=self.stop_event)
            last_end = rec.get("t_end", time.monotonic())
            with self._lock:
                self.records.append(rec)

    def start(self) -> None:
        for i in range(self.clients):
            t = threading.Thread(target=self._client, daemon=True,
                                 name=f"client-{i}")
            t.start()
            self._threads.append(t)

    def stop(self, timeout: float = 30.0) -> None:
        self.stop_event.set()
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.1, deadline - time.monotonic()))

    def alive(self) -> int:
        return sum(t.is_alive() for t in self._threads)


def open_schedule(traffic: dict, seconds: float) -> list:
    """Due times (seconds from the loop's start) of an open loop:
    evenly spaced at `rate_rps`, and with `burst` a group of `size`
    requests due together every `every_s`. Deterministic: the seed
    changes order and content, never the arrivals."""
    due, rate = [], float(traffic["rate_rps"])
    burst = traffic.get("burst")
    if burst:
        t = 0.0
        while t < seconds:
            due += [t] * int(burst["size"])
            t += float(burst["every_s"])
    else:
        k = 0
        while k / rate < seconds:
            due.append(k / rate)
            k += 1
    return due


class OpenLoop:
    """Sends the n-th request when it is due, one thread per request in
    flight; `t_due` is what latency counts from, and `late_s` says how
    late the generator itself ran."""

    def __init__(self, port: int, mix: Mix, seconds: float):
        self.port, self.mix = port, mix
        self.due = open_schedule(mix.traffic, seconds)
        self.records, self.turnarounds = [], []
        self._lock = threading.Lock()
        self.stop_event = threading.Event()
        self._threads = []
        self._pacer = None

    def _one(self, item: dict, t_due: float) -> None:
        rec = stream_chat(self.port, item, stop=self.stop_event)
        rec["t_due"] = t_due
        rec["late_s"] = rec["t_send"] - t_due
        with self._lock:
            self.records.append(rec)

    def _pace(self) -> None:
        t0 = time.monotonic()
        for n, offset in enumerate(self.due):
            wait = t0 + offset - time.monotonic()
            if wait > 0 and self.stop_event.wait(wait):
                return
            if self.stop_event.is_set():
                return
            t = threading.Thread(target=self._one, daemon=True,
                                 args=(self.mix.item(n), t0 + offset))
            t.start()
            self._threads.append(t)

    def start(self) -> None:
        self._pacer = threading.Thread(target=self._pace, daemon=True)
        self._pacer.start()

    def stop(self, timeout: float = 30.0) -> None:
        self.stop_event.set()
        deadline = time.monotonic() + timeout
        for t in [self._pacer] + self._threads:
            t.join(max(0.1, deadline - time.monotonic()))

    def alive(self) -> int:
        return sum(t.is_alive() for t in self._threads)


def make_loop(port: int, mix: Mix, seconds: float):
    kind = mix.traffic["loop"]
    if kind == "closed":
        return ClosedLoop(port, mix, int(mix.traffic["clients"]))
    if kind == "open":
        return OpenLoop(port, mix, seconds)
    raise SpecError(f"unknown loop kind {kind!r}")
