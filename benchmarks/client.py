#!/usr/bin/env python3
"""The load generator: a child process of the serve runner.

Standard library only; it never imports jax, so it can never reach for
the chip its parent holds.  It reads one JSON spec (argv[1]), offers
the mix's load to ``url`` until ``t_stop`` on the shared monotonic
clock (CLOCK_MONOTONIC is one clock for every process of a Linux
host), lets the requests in flight finish, writes every request's
record to ``out`` and exits.  The loop is closed: ``clients`` callers,
each sending its next request when the last one returned.  Times are
the client's: a request's latency runs from the moment it was sent to
the last byte of the answer.
"""

import http.client
import json
import os
import sys
import threading
import time
import urllib.parse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import traffic  # noqa: E402


class Sender:
    def __init__(self, spec):
        self.spec = spec
        url = urllib.parse.urlparse(spec["url"])
        self.host, self.port = url.hostname, url.port
        self.records = []
        self.lock = threading.Lock()
        # how many requests of each class keep their output ids for the
        # parent's reference check
        self.keep = dict.fromkeys(
            (c["name"] for c in spec["mix"]["prompt_classes"]),
            int(spec["mix"].get("check_per_class", 0)))

    def make(self, client, index):
        s = self.spec
        return traffic.request(s["mix_name"], s["mix"], s["seed"],
                               s["vocab"], client, index)

    def send(self, req):
        body = json.dumps({"prompt": req["prompt"],
                           "max_tokens": req["max_tokens"]}).encode()
        rec = {"client": req["client"], "index": req["index"],
               "class": req["class"], "n_prompt": len(req["prompt"]),
               "max_tokens": req["max_tokens"]}
        rec["t_send"] = time.monotonic()
        try:
            conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.spec["timeout_s"])
            try:
                conn.request("POST", "/generate", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                doc = json.loads(resp.read())
                rec["status"] = resp.status
            finally:
                conn.close()
            rec["t_done"] = time.monotonic()
            rec["error"] = doc.get("error")
            rec["n_generated"] = doc.get("n_generated", 0)
            rec["ttft_s"] = doc.get("ttft_s")
            rec["preemptions"] = doc.get("preemptions", 0)
            output = doc.get("output_ids")
        except (OSError, ValueError, http.client.HTTPException) as e:
            rec["t_done"] = time.monotonic()
            rec.update(status=0, error=repr(e), n_generated=0, ttft_s=None)
            output = None
        with self.lock:
            in_window = (self.spec["t_open"] <= rec["t_done"]
                         <= self.spec["t_close"])
            if (output and in_window and not rec["error"]
                    and self.keep.get(rec["class"], 0) > 0):
                self.keep[rec["class"]] -= 1
                rec["output_ids"] = output
            self.records.append(rec)

    def closed_client(self, client):
        index = 0
        while time.monotonic() < self.spec["t_stop"]:
            self.send(self.make(client, index))
            index += 1


def main(argv):
    with open(argv[1]) as f:
        spec = json.load(f)
    sender = Sender(spec)
    mix = spec["mix"]
    if mix["loop"] != "closed":
        raise SystemExit(f"unknown loop {mix['loop']!r}")
    threads = [threading.Thread(target=sender.closed_client, args=(c,))
               for c in range(int(mix["clients"]))]
    delay = spec["t_start"] - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tmp = spec["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(sender.records, f)
    os.replace(tmp, spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
