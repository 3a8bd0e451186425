"""What a test may hold a server's device state against.

A plain ``device_loop_ticks=1`` server reads a launch after it has
made the next one (``core/serving.py``, "Deferred harvest"), so after
``step()`` the device's ``last_logits`` of a live row belong to a
sequence one token longer than the host's ``prompt + tokens``: the
token the launch in flight sampled, which the host commits a step
later. ``ServedRows`` pairs each row's logits with the sequence they
were computed from, holding a row back until the host knows it.
"""

import contextlib

import jax
import numpy as np

from paddlefleetx_tpu.observability import metrics


@contextlib.contextmanager
def device_reads():
    """Every ``np.asarray`` of a ``jax.Array`` made inside the block
    (the way the server brings an array home), as whether the array
    was ready when it was asked for: a read of one that was not is a
    wait for the device."""
    seen, asarray = [], np.asarray

    def spy(a, *args, **kw):
        if isinstance(a, jax.Array):
            seen.append(a.is_ready())
        return asarray(a, *args, **kw)
    np.asarray = spy
    try:
        yield seen
    finally:
        np.asarray = asarray


def ends_a_prompt_without_a_read(srv, prompt):
    """Submit ``prompt`` to an empty server and step until its last
    chunk has gone down: no step on the way reads the device (no
    launch is unread yet, so there is nothing a step may read), and
    the step that ends the prompt launches the first tick with the
    new slot in it."""
    reg = metrics.get_registry()
    reads = reg.counter("serving/d2h_reads")
    rid = srv.submit(prompt)
    chunks = 0
    with device_reads() as seen:
        while srv._inflight is None:
            srv.step()
            chunks += srv.last_step.chunks
    assert chunks == -(-len(prompt) // srv._chunk)
    assert all(seen), seen          # nothing that was still running
    assert reg.counter("serving/d2h_reads") == reads
    assert reg.counter("serving/activations/device_row") >= 1
    assert reg.counter("serving/activations/host_row") == 0
    (slot, req), = srv._inflight.rows
    assert req["id"] == rid and req["active"] and req["ahead"] == 1
    return rid


class ServedRows:
    """``after_step()`` after every ``srv.step()``: ``(request,
    sequence, logits row)`` of every live row whose sequence the host
    now knows, this step's or an earlier one's."""

    def __init__(self, srv):
        self.srv = srv
        self.held = []

    def after_step(self):
        logits = np.asarray(self.srv._state.last_logits)
        rows = self.held + [
            (req, len(req["prompt"]) + len(req["tokens"]) + req["ahead"],
             logits[slot])
            for slot, req in enumerate(self.srv._slots)
            if req is not None and req.get("active")]
        self.held, out = [], []
        for req, n, row in rows:
            seq = req["prompt"] + req["tokens"]
            if len(seq) >= n:
                out.append((req, seq[:n], row))
            else:
                self.held.append((req, n, row))
        return out
