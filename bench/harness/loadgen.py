"""Open-loop load generator. Stdlib only: it runs as a child process that
never imports jax, so it does not share the engine's interpreter lock, and the
parent keeps the chip.

    python3 loadgen.py --port P --plan plan.json --out results.jsonl --t0 <monotonic s>

``plan.json``: {"seed", "vocab", "requests": [{"index", "due", "prompt_tokens",
"max_tokens", "phase"}]}; ``due`` is seconds after ``t0`` on CLOCK_MONOTONIC,
which parent and child share. Each request is one streamed HTTP
``/v1/completions`` with a token-id prompt, sent when it is due whether or not
earlier ones have finished. One JSON line per request comes back: when it was
due, when it was sent, the arrival time of every token, status and finish
reason."""

from __future__ import annotations

import argparse
import http.client
import json
import random
import sys
import threading
import time


def prompt_ids(seed, index, n, vocab):
    """The prompt of request ``index``: ``n`` seeded token ids, no two requests alike."""
    rng = random.Random((seed << 20) + index)
    return [rng.randrange(vocab) for _ in range(n)]


def _one(port, t0, req, body, out):
    rec = {"index": req["index"], "phase": req["phase"], "due": req["due"], "status": None,
           "prompt_tokens": req["prompt_tokens"], "max_tokens": req["max_tokens"],
           "token_times": [], "token_ids": [], "finish_reason": None}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        rec["sent"] = time.monotonic() - t0
        conn.request("POST", "/v1/completions", body, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(300).decode(errors="replace")
        else:
            for raw in resp:
                if not raw.startswith(b"data: ") or raw.startswith(b"data: [DONE]"):
                    continue
                now = time.monotonic() - t0
                doc = json.loads(raw[6:])
                if doc.get("object") == "error":
                    rec["error"] = json.dumps(doc)[:300]
                    break
                choice = doc["choices"][0]
                if "token" in choice:
                    rec["token_times"].append(now)
                    rec["token_ids"].append(choice["token"])
                if choice.get("finish_reason") is not None:
                    rec["finish_reason"] = choice["finish_reason"]
    except Exception as e:  # recorded, and counted as a failed request by the parent
        rec["error"] = repr(e)
    finally:
        conn.close()
        rec["done"] = time.monotonic() - t0
        out.append(rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--t0", type=float, required=True)
    args = ap.parse_args()
    plan = json.load(open(args.plan))
    reqs = sorted(plan["requests"], key=lambda r: r["due"])
    bodies = [json.dumps({"prompt": prompt_ids(plan["seed"], r["index"], r["prompt_tokens"], plan["vocab"]),
                          "max_tokens": r["max_tokens"], "stream": True}) for r in reqs]
    out, threads = [], []
    for req, body in zip(reqs, bodies):
        wait = args.t0 + req["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=_one, args=(args.port, args.t0, req, body, out), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(600)
    with open(args.out, "w") as f:
        for rec in sorted(out, key=lambda r: r["index"]):
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
