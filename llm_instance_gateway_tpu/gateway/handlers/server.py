"""Handler server: per-request dispatch loop over processing messages.

Parity: reference ``pkg/ext-proc/handlers/server.go:17-128`` — ``NewServer``
wiring, the per-stream ``RequestContext``, the phase dispatch, and the
RESOURCE_EXHAUSTED -> 429 immediate-response mapping (:95-113).  Transports
(gRPC stream, HTTP proxy) feed messages through ``Server.process``.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field

from llm_instance_gateway_tpu.gateway.datastore import Datastore
from llm_instance_gateway_tpu.gateway.handlers import request as request_handlers
from llm_instance_gateway_tpu.gateway.handlers import response as response_handlers
from llm_instance_gateway_tpu.gateway.handlers.messages import (
    ProcessingMessage,
    ProcessingResult,
    RequestBody,
    RequestHeaders,
    RequestTrailers,
    ResponseBody,
    ResponseHeaders,
    ResponseTrailers,
)
from llm_instance_gateway_tpu.gateway.handlers.response import Usage
from llm_instance_gateway_tpu.gateway.scheduling.scheduler import SchedulingError
from llm_instance_gateway_tpu.gateway.types import Pod

logger = logging.getLogger(__name__)

DEFAULT_TARGET_POD_HEADER = "target-pod"  # main.go:34 flag default
# Second hop of a disaggregated pick: the decode replica's address.  The
# standalone proxy relays the handoff between the two hops itself; the
# ext-proc transport surfaces the header for an Envoy-side implementation.
DEFAULT_DECODE_POD_HEADER = "x-decode-pod"


@dataclass
class RequestContext:
    """Per-HTTP-request state shared across phases (server.go:124-128)."""

    target_pod: Pod | None = None
    # Disaggregated pools: the decode-role replica of a two-stage pick
    # (None = single-hop).  target_pod is then the prefill hop.
    decode_pod: Pod | None = None
    model: str = ""
    resolved_target_model: str = ""
    # End-to-end tracing (tracing.py): honored from the inbound
    # x-lig-trace-id header or minted in the headers/body phase, injected
    # into the upstream header set, and echoed in every response.
    trace_id: str = ""
    # Scheduling attribution for the admission span: time parked in the
    # admission queue, and the (prefill, decode) pick split of a two-stage
    # disaggregated pick (None = single-hop).
    admission_wait_s: float = 0.0
    pick_hops_s: tuple | None = None
    usage: Usage = field(default_factory=Usage)
    # Fairness quota memo (handlers/request.py): the tenant bucket is
    # charged ONCE per client request; proxy retry attempts and hedge
    # re-picks reuse/flag the context and replay the decision instead of
    # spending another token per internal attempt.
    fairness_charged: bool = False
    fairness_demoted_to: str | None = None
    # The standalone proxy's stall clock (tracing.LoopClock.marks) at the
    # handler's entry: the stream's record says how much loop lag and
    # stall the request lived through.
    loop_marks: tuple | None = None


class ProcessingError(Exception):
    """Fatal processing error.

    ``status`` is the HTTP status the standalone proxy returns (the gRPC
    transport maps any ProcessingError to stream abort, like the reference's
    non-ResourceExhausted branch at server.go:110-112).  Malformed/unroutable
    client input is 400; internal failures 500.
    """

    def __init__(self, msg: str, status: int = 500):
        super().__init__(msg)
        self.status = status


class Server:
    def __init__(
        self,
        scheduler,
        datastore: Datastore,
        target_pod_header: str = DEFAULT_TARGET_POD_HEADER,
        decode_pod_header: str = DEFAULT_DECODE_POD_HEADER,
    ):
        self.scheduler = scheduler
        self.datastore = datastore
        self.target_pod_header = target_pod_header
        self.decode_pod_header = decode_pod_header
        # Fairness/quota admission gate (gateway/fairness.py, wired by the
        # proxy): consulted in the body phase BEFORE scheduling, so an
        # over-quota tenant's request is demoted one criticality tier on
        # every transport (HTTP proxy AND gRPC ext-proc).  None = off.
        self.fairness = None

    def process(
        self, req_ctx: RequestContext, msg: ProcessingMessage
    ) -> ProcessingResult:
        """Dispatch one phase message (server.go:58-120).

        Sheddable-drop becomes ``immediate_status=429``; malformed input and
        internal errors raise ``ProcessingError`` for the transport to map.
        """
        try:
            if isinstance(msg, RequestHeaders):
                return request_handlers.handle_request_headers(req_ctx, msg)
            if isinstance(msg, RequestBody):
                return request_handlers.handle_request_body(self, req_ctx, msg)
            if isinstance(msg, ResponseHeaders):
                return response_handlers.handle_response_headers(req_ctx, msg)
            if isinstance(msg, ResponseBody):
                return response_handlers.handle_response_body(req_ctx, msg)
            if isinstance(msg, RequestTrailers):
                return ProcessingResult(phase="request_trailers")
            if isinstance(msg, ResponseTrailers):
                return ProcessingResult(phase="response_trailers")
        except SchedulingError as e:
            if e.shed:
                # server.go:100-109: ResourceExhausted -> 429 TooManyRequests.
                logger.info("shedding request: %s", e)
                return ProcessingResult(phase="immediate", immediate_status=429)
            raise ProcessingError(f"failed to find target pod: {e}") from e
        except request_handlers.RequestError as e:
            raise ProcessingError(str(e), status=400) from e
        except response_handlers.ResponseError as e:
            raise ProcessingError(str(e), status=500) from e
        raise ProcessingError(f"unknown request type {type(msg).__name__}")
