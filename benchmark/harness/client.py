"""One streaming chat completion over HTTP, timed on the client's clock.

The request asks for `include_tokens` (the field the fleet router uses), so
every SSE delta names the token ids it carries: tokens are counted and
compared as ids, never guessed from text. Times are `time.monotonic()`.
"""

from __future__ import annotations

import http.client
import json
import random
import string
import time

CONNECT_TRIES = 4
ALPHABET = string.ascii_letters + string.digits + " .,;:!?-"


def prompt_text(n_chars: int, rnd: random.Random) -> str:
    """Random printable bytes: with the byte-level `.t` a character is a
    token, and no two prompts share more than the chat template."""
    return "".join(rnd.choices(ALPHABET, k=n_chars))


def stream_chat(port: int, rid: str, content: str, max_tokens: int,
                due: float, timeout: float = 300.0) -> dict:
    """Send one request now; `due` is when it was due. Never raises: an
    error is a record with `error` set, and counts as failed."""
    rec = {"id": rid, "due": due, "max_tokens": max_tokens, "ids": [],
           "deltas": [], "first": None, "last": None, "finish": None,
           "error": None, "meta": None, "retries": 0}
    body = json.dumps({
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0, "stream": True,
        "include_tokens": True,
    })
    rec["sent"] = time.monotonic()
    headers = {"Content-Type": "application/json", "Connection": "close",
               "x-dllama-request": rid}
    try:
        for attempt in range(CONNECT_TRIES):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
            try:
                conn.request("POST", "/v1/chat/completions", body, headers)
                resp = conn.getresponse()
                break
            except (ConnectionResetError, ConnectionRefusedError, BrokenPipeError):
                # the server's listen queue (5) overflowed before it read the
                # request: send again, as a client's TCP stack would
                conn.close()
                rec["retries"] = attempt + 1
                if attempt == CONNECT_TRIES - 1:
                    raise
                time.sleep(0.05 * (attempt + 1))
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return rec
        while True:
            line = resp.readline()
            if not line:
                rec["error"] = rec["error"] or "stream ended without [DONE]"
                break
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            data = line[6:].strip()
            if data == b"[DONE]":
                resp.read()  # the chunked body's terminator, so the close is clean
                break
            frame = json.loads(data)
            if "error" in frame:
                rec["error"] = json.dumps(frame["error"])[:300]
                continue
            ids = frame.get("dllama_tokens") or []
            if ids:
                rec["ids"] += ids
                rec["deltas"].append((now, len(ids)))
                rec["first"] = rec["first"] or now
                rec["last"] = now
            choice = frame["choices"][0]
            if choice.get("finish_reason"):
                rec["finish"] = choice["finish_reason"]
                rec["meta"] = frame.get("dllama")
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
        rec["done"] = time.monotonic()
    return rec
