"""Tuner-as-a-service CLI: daemon and client ends of one socket.

Serve (long-lived; one fleet + one plan store for every request it ever
answers):

    python -m repro.launch.tune_serve serve --store /var/tune-store \
        --socket /tmp/tuner.sock

Client (per request; returns the tuned plan as JSON on stdout):

    python -m repro.launch.tune_serve tune --socket /tmp/tuner.sock \
        --arch granite-3-2b --shape train_4k --algo mcts_1s
    python -m repro.launch.tune_serve stats --socket /tmp/tuner.sock
    python -m repro.launch.tune_serve shutdown --socket /tmp/tuner.sock
"""
from __future__ import annotations

import argparse
import json
import os
import socket


class TuneClient:
    """One JSON-lines request/response per call over the daemon socket."""

    def __init__(self, socket_path: str, timeout: float = 600.0):
        self.socket_path = socket_path
        self.timeout = timeout

    def call(self, msg: dict) -> dict:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.settimeout(self.timeout)
            s.connect(self.socket_path)
            with s.makefile("rwb") as f:
                f.write((json.dumps(msg) + "\n").encode())
                f.flush()
                line = f.readline()
        if not line:
            raise ConnectionError("daemon closed the connection")
        return json.loads(line)

    def tune(self, arch: str, shape: str, **settings) -> dict:
        return self.call({"op": "tune", "arch": arch, "shape": shape,
                          **settings})

    def stats(self) -> dict:
        return self.call({"op": "stats"})

    def ping(self) -> dict:
        return self.call({"op": "ping"})

    def shutdown(self) -> dict:
        return self.call({"op": "shutdown"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    sv = sub.add_parser("serve", help="run the daemon")
    sv.add_argument("--store", required=True, help="plan-store root dir")
    sv.add_argument("--socket", required=True, help="unix socket path")
    sv.add_argument("--measure", default="none",
                    choices=["none", "stub", "real"],
                    help="shared measurement fleet for *real* algos "
                         "(stub = deterministic XLA-free target)")
    sv.add_argument("--max-requests", type=int, default=None,
                    help="exit after N tune requests (tests/CI smoke)")
    sv.add_argument("--read-timeout", type=float, default=30.0,
                    help="per-connection socket read timeout in seconds "
                         "(a silent client is closed, not waited on)")
    sv.add_argument("--queue-size", type=int, default=16,
                    help="bounded tune-request queue; a full queue answers "
                         "'overloaded' with a retry_after_s hint")
    sv.add_argument("--checkpoint-every", type=int, default=4,
                    help="persist a resumable search checkpoint every K "
                         "decision rounds (0 disables crash resume)")
    sv.add_argument("--deadline-s", type=float, default=None,
                    help="default per-request search deadline; requests "
                         "override with their own deadline_s")
    sv.add_argument("--round-delay", type=float, default=0.0,
                    help=argparse.SUPPRESS)  # fault-injection: slow rounds
    sv.add_argument("--no-recover", action="store_true",
                    help="skip write-ahead-journal replay on startup")

    def add_request_args(p):
        p.add_argument("--socket", required=True)
        p.add_argument("--arch", required=True)
        p.add_argument("--shape", required=True)
        p.add_argument("--algo", default="mcts_30s")
        p.add_argument("--mesh", default="single", choices=["single", "multi"])
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--budget-s", type=float, default=None)
        p.add_argument("--n-standard", type=int, default=15)
        p.add_argument("--n-greedy", type=int, default=1)
        p.add_argument("--noise-sigma", type=float, default=0.0)
        p.add_argument("--deadline-s", type=float, default=None,
                       help="interrupt the search at the next round "
                            "boundary after this many seconds; the "
                            "response is best-so-far with interrupted "
                            "provenance, and a repeat request resumes "
                            "from the checkpoint")

    tn = sub.add_parser("tune", help="submit one tuning request")
    add_request_args(tn)

    st = sub.add_parser("stats", help="daemon counters")
    st.add_argument("--socket", required=True)
    sd = sub.add_parser("shutdown", help="stop the daemon")
    sd.add_argument("--socket", required=True)

    args = ap.parse_args(argv)

    if args.cmd == "serve":
        # the daemon's JAX user (jit pricing) prices plans on the host; it
        # and the fleet workers it spawns stay off the chip, which belongs
        # to the one process that runs the tuned program
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from repro.service.daemon import TunerService, serve_forever

        service = TunerService(
            args.store, measure=args.measure,
            checkpoint_every=args.checkpoint_every,
            deadline_s=args.deadline_s,
            round_delay_s=args.round_delay,
        )
        served = serve_forever(service, args.socket,
                               max_requests=args.max_requests,
                               read_timeout_s=args.read_timeout,
                               queue_size=args.queue_size,
                               recover=not args.no_recover)
        print(f"[tune_serve] served {served} request(s)")
        return 0

    client = TuneClient(args.socket)
    if args.cmd == "stats":
        out = client.stats()
    elif args.cmd == "shutdown":
        out = client.shutdown()
    else:
        settings = dict(
            algo=args.algo, mesh=args.mesh,
            seed=args.seed, time_budget_s=args.budget_s,
            n_standard=args.n_standard, n_greedy=args.n_greedy,
            noise_sigma=args.noise_sigma,
        )
        if args.deadline_s is not None:
            settings["deadline_s"] = args.deadline_s
        out = client.tune(args.arch, args.shape, **settings)
    print(json.dumps(out, indent=1, default=str))
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
