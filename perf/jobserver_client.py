"""The jobserver in this process, driven from the other side of its socket.

Copied from ``chip_smoke.py`` (``Server``), the sound way to drive a tenant:
``cli._make_server`` is what ``harmony-tpu start-jobserver`` runs — the one
process that opens the chips, compile cache placed by utils/compcache.py —
and ``CommandSender`` is the jax-free client ``harmony-tpu submit`` uses.
Everything the benchmark asks of the program goes over that TCP endpoint:
SUBMIT / STATUS / WAIT / SHUTDOWN.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional


class Server:
    def __init__(self, num_executors: int, scheduler: Optional[str] = None,
                 scheduler_args: Optional[Dict[str, Any]] = None) -> None:
        from harmony_tpu import cli
        from harmony_tpu.jobserver.client import CommandSender

        if scheduler is None:
            self.server = cli._make_server(num_executors)
        else:  # a traffic mix may name another scheduler (dotted path)
            from harmony_tpu.config.base import resolve_symbol
            from harmony_tpu.jobserver.server import JobServer
            from harmony_tpu.utils.compcache import enable_compile_cache

            enable_compile_cache()
            self.server = JobServer(
                num_executors=num_executors,
                scheduler=resolve_symbol(scheduler)(**(scheduler_args or {})))
            self.server.start()
        self.port = self.server.serve_tcp(0)
        self._sender = CommandSender

    def client(self):
        """A client of its own for each thread that talks to the server."""
        return self._sender(self.port)

    def submit(self, config) -> None:
        reply = self.client().send_job_submit_command(config)
        if not reply.get("ok"):
            raise RuntimeError(f"SUBMIT {config.job_id}: {reply}")

    def status(self, client=None) -> Dict[str, Any]:
        reply = (client or self.client()).send_status_command()
        if not reply.get("ok"):
            raise RuntimeError(f"STATUS: {str(reply)[:400]}")
        return reply

    def shutdown(self, timeout: float = 120.0) -> None:
        """SHUTDOWN over TCP, then wait for the drain (the command only
        starts it)."""
        self.client().send_shutdown_command()
        deadline = time.monotonic() + timeout
        while self.server.state != "CLOSED":
            if time.monotonic() > deadline:
                raise RuntimeError(f"jobserver did not close in {timeout} s")
            time.sleep(0.05)
