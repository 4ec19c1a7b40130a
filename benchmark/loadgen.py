"""The open-loop load generator: a process of its own that imports nothing
but the standard library, so that it shares no interpreter lock with the
server's scheduler. Usage (by ``loops/serve_open.py``):

    python3 benchmark/loadgen.py < two lines on standard input

Line 1: ``{"port": p, "timeout_s": s, "requests": [{"id", "due", "tokens",
"max_tokens"}]}``. It then prints ``ready`` and waits for line 2, ``go <t0>``:
the window opens at ``t0`` on ``time.monotonic()`` (one clock for all
processes of a Linux host). Every request is sent ``due`` seconds after
``t0`` whatever the server does, and the last line printed is the JSON list
of ``{"id", "sent", "done", "code", "reply"}`` (times in seconds after
``t0``; ``code`` 0 with ``error`` where no reply came).
"""

import http.client
import json
import sys
import threading
import time


def _one(port, timeout_s, t0, req, out):
    body = json.dumps({"tokens": req["tokens"],
                       "max_tokens": req["max_tokens"]}).encode()
    rec = {"id": req["id"], "code": 0}
    delay = t0 + req["due"] - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    rec["sent"] = time.monotonic() - t0
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", port, timeout=req.get("timeout_s", timeout_s))
        try:
            conn.request("POST", "/generate", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
            rec["done"] = time.monotonic() - t0
            rec["code"] = resp.status
            rec["reply"] = json.loads(payload)
        finally:
            conn.close()
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["done"] = time.monotonic() - t0
        rec["error"] = repr(e)
    out.append(rec)


def main() -> int:
    job = json.loads(sys.stdin.readline())
    print("ready", flush=True)
    word, t0 = sys.stdin.readline().split()
    if word != "go":
        return 2
    t0 = float(t0)
    out = []
    threads = [
        threading.Thread(target=_one, args=(
            job["port"], job["timeout_s"], t0, req, out), daemon=True)
        for req in job["requests"]
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    print(json.dumps(sorted(out, key=lambda r: r["id"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
