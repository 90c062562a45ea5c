"""The load: closed-loop streaming clients with a timestamp on every token.

Copied in substance from ``tritonclient_tpu/genai_perf`` (``_Worker``): one
gRPC client and one bidirectional stream per client, ``perf_counter_ns``
taken in the stream callback, one request in flight per client. The
original stays in the program; this copy is the yardstick and does not
change when the program does. What differs: requests come from the seeded
``RequestSource``, every request's tokens and arrival times are kept (the
output check and the metric readers need them), and nothing is summarised
here.
"""

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from benchmarks.traffic import Request, RequestSource

RESPONSE_WAIT_S = 120.0


@dataclass
class RequestLog:
    """What one request did, on the client's clock (``perf_counter_ns``)."""

    request: Request
    client: int
    sent_ns: int
    token_ns: List[int] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done_ns: int = 0
    error: Optional[str] = None    # set: the request failed, has no latency


class Client:
    """One closed-loop requester on its own stream."""

    def __init__(self, address: str, model_name: str, vocab_size: int,
                 index: int):
        self._address = address
        self._model = model_name
        self._vocab = vocab_size
        self.index = index
        self.logs: List[RequestLog] = []
        self._client = None
        self._responses: "queue.Queue" = queue.Queue()   # replaced by open()

    def open(self):
        import tritonclient_tpu.grpc as grpcclient

        self._grpc = grpcclient
        self._client = grpcclient.InferenceServerClient(self._address)
        responses: "queue.Queue" = queue.Queue()
        self._responses = responses
        self._client.start_stream(
            callback=lambda result, error: responses.put(
                (time.perf_counter_ns(), result, error)))

    def close(self):
        client, self._client = self._client, None
        if client is None:
            return
        try:
            client.stop_stream()
        finally:
            client.close()

    def _reopen(self):
        # After a failure the request's late responses may still arrive: a
        # fresh stream and queue keep later samples attributable.
        try:
            self.close()
        except Exception:   # noqa: BLE001 - the stream is already broken
            pass
        self.open()

    def _tensor(self, name: str, value: np.ndarray, datatype: str):
        tensor = self._grpc.InferInput(name, list(value.shape), datatype)
        tensor.set_data_from_numpy(value)
        return tensor

    def send(self, request: Request,
             due_ns: Optional[int] = None) -> RequestLog:
        """One request, sent and read to its final response. An open loop
        gives the time it was due: it is timed from then."""
        inputs = [
            self._tensor("INPUT_IDS", request.prompt, "INT32"),
            self._tensor("MAX_TOKENS",
                         np.array([request.max_tokens], np.int32), "INT32"),
        ]
        log = RequestLog(request, self.index,
                         time.perf_counter_ns() if due_ns is None else due_ns)
        self.logs.append(log)
        try:
            self._client.async_stream_infer(
                self._model, inputs, enable_empty_final_response=True)
            while True:
                t_recv, result, error = self._responses.get(
                    timeout=RESPONSE_WAIT_S)
                if error is not None:
                    raise RuntimeError(f"stream error: {error}")
                out = result.as_numpy("OUTPUT_IDS")
                if out is not None and out.size:
                    log.token_ns.append(t_recv)
                    log.tokens.append(int(out.reshape(-1)[0]))
                final = result.get_response().parameters.get(
                    "triton_final_response")
                if final is not None and final.bool_param:
                    break
        except queue.Empty:
            log.error = f"no response within {RESPONSE_WAIT_S:.0f} s"
        except Exception as e:   # noqa: BLE001 - a failed request is counted
            log.error = f"{type(e).__name__}: {e}"
        log.done_ns = time.perf_counter_ns()
        if log.error is None:
            if len(log.tokens) != request.max_tokens:
                log.error = (f"short stream: {len(log.tokens)} of "
                             f"{request.max_tokens} tokens")
            elif not all(0 <= t < self._vocab for t in log.tokens):
                log.error = "token out of range"
        if log.error is not None and self._client is not None:
            self._reopen()
        return log

    def run_until(self, source: RequestSource, end_ns: int,
                  stop: threading.Event):
        """Closed loop: the next request goes out when the last one is
        complete, until ``end_ns`` on this clock. A request sent before the
        end is read to its end, however late."""
        while time.perf_counter_ns() < end_ns and not stop.is_set():
            self.send(source.take())


def open_clients(address: str, model_name: str, vocab_size: int,
                 count: int) -> List[Client]:
    """``count`` clients, each with its stream open."""
    clients = [Client(address, model_name, vocab_size, i)
               for i in range(count)]
    for client in clients:
        client.open()
    return clients
