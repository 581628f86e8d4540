"""stream_encode_ms - layer: wire. Source: POLL's stage table
(program_span), POLLed after FETCH. Median per task of `frame_encode`
(Arrow IPC serialisation of a part) plus `frame_send` (the socket write
and its wait): the wire's cost inside FETCH, apart from the service's.
0 for a task that streams no part (a shuffle write). Moves
latency_p50_ms."""

from ._stages import median_wall_ms


def read(run: dict):
    return median_wall_ms(run, "frame_encode", "frame_send")
