"""What a test may hold a server's device state against.

A plain ``device_loop_ticks=1`` server reads a launch after it has
made the next one (``core/serving.py``, "Deferred harvest"), so after
``step()`` the device's ``last_logits`` of a live row belong to a
sequence one token longer than the host's ``prompt + tokens``: the
token the launch in flight sampled, which the host commits a step
later. ``ServedRows`` pairs each row's logits with the sequence they
were computed from, holding a row back until the host knows it.
"""

import numpy as np


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
