"""Shared example plumbing: arg parsing + optional self-hosted server.

The reference examples assume a live Triton (localhost:8000/8001); these
examples accept the same -u/-v flags and additionally ``--fixture`` to
self-start the in-process JAX server so every example runs hermetically
(the fixture tier the reference lacks, SURVEY.md §4).
"""

import argparse
import contextlib


def example_parser(description: str, default_port: int = 8001):
    parser = argparse.ArgumentParser(description=description)
    parser.add_argument(
        "-u", "--url", default=f"localhost:{default_port}",
        help="server address host:port",
    )
    parser.add_argument("-v", "--verbose", action="store_true")
    parser.add_argument(
        "--fixture", action="store_true",
        help="start an in-process JAX server and run against it",
    )
    return parser


@contextlib.contextmanager
def maybe_fixture_server(args, models=None, grpc=True):
    """Yields the URL to use; starts an in-process server under --fixture."""
    if not args.fixture:
        yield args.url
        return
    from tritonclient_tpu.server import InferenceServer

    with InferenceServer(models=models) as server:
        yield server.grpc_address if grpc else server.http_address
