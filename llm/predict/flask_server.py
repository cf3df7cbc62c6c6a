"""Streaming HTTP chat server.

Counterpart of ``/root/reference/llm/predict/flask_server.py`` (235 LoC: streaming
HTTP on flask + the get_output SysV message queue). Stdlib-only (no flask in this
image): ``ThreadingHTTPServer`` + server-sent-event streaming straight from the
engine's token callbacks — the IPC hop disappears because the engine is in-process.

POST /generate  {"src": str, "max_length"?: int, "stream"?: bool}
GET  /health
"""

from __future__ import annotations

import json
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

from paddlenlp_tpu.trainer import PdArgumentParser
from paddlenlp_tpu.utils.env import enable_compile_cache
from paddlenlp_tpu.utils.log import logger
from predictor import BlockPredictor, PredictorArgument, create_predictor


def make_handler(predictor, lock: threading.Lock):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            logger.debug(fmt % args)

        def do_GET(self):
            if self.path == "/health":
                body = json.dumps({"status": "ok"}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_response(404)
                self.end_headers()

        def do_POST(self):
            if self.path != "/generate":
                self.send_response(404)
                self.end_headers()
                return
            length = int(self.headers.get("Content-Length", 0))
            try:
                payload = json.loads(self.rfile.read(length) or b"{}")
                text = payload["src"]
            except (json.JSONDecodeError, KeyError) as e:
                body = json.dumps({"error": f"bad request: {e}"}).encode()
                self.send_response(400)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            stream = bool(payload.get("stream", False))
            if "max_length" in payload:
                predictor.args.max_length = int(payload["max_length"])
            with lock:  # one generation at a time per engine (batching inside)
                if stream and isinstance(predictor, BlockPredictor):
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Cache-Control", "no-cache")
                    self.end_headers()
                    for piece in predictor.stream_predict(text):
                        self.wfile.write(f"data: {json.dumps({'token': piece})}\n\n".encode())
                        self.wfile.flush()
                    self.wfile.write(b"data: [DONE]\n\n")
                else:
                    out = predictor.predict([text])[0]
                    body = json.dumps({"output": out}, ensure_ascii=False).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

    return Handler


def serve(predictor, port: int = 8011):
    server = ThreadingHTTPServer(("0.0.0.0", port), make_handler(predictor, threading.Lock()))
    logger.info(f"serving on :{port} (POST /generate)")
    server.serve_forever()


def serve_v1(predictor, port: int = 8011):
    """Serve through the continuous-batching runtime (paddlenlp_tpu.serving):
    concurrent requests share the engine's running batch instead of taking
    turns behind the legacy per-request lock; adds /v1/completions SSE
    streaming, admission control (429/503) and /metrics. Needs --mode block."""
    from paddlenlp_tpu.serving import SchedulerConfig, ServingServer

    if not isinstance(predictor, BlockPredictor):
        raise ValueError("--api v1 needs the paged engine: run with --mode block")
    server = ServingServer(
        predictor.engine,
        tokenizer=predictor.tokenizer,
        scheduler_config=SchedulerConfig(max_inflight=4 * predictor.args.batch_size),
        max_src_tokens=predictor.args.src_length,
    )
    server.run(port=port)


def main():
    enable_compile_cache()
    parser = PdArgumentParser((PredictorArgument,))
    (args, remaining) = parser.parse_args_into_dataclasses(return_remaining_strings=True)
    port, api = 8011, "legacy"
    for i, r in enumerate(remaining):
        if r in ("--port", "--api"):
            if i + 1 >= len(remaining):
                raise SystemExit(f"{r} requires a value")
            if r == "--port":
                port = int(remaining[i + 1])
            else:
                api = remaining[i + 1]
    predictor = create_predictor(args)
    if api == "v1":
        serve_v1(predictor, port)
    else:
        serve(predictor, port)


if __name__ == "__main__":
    main()
