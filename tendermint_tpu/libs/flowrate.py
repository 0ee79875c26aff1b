"""Flow-rate monitoring + token-bucket limiting (reference libs/flowrate/).

The reference's flowrate.Monitor (libs/flowrate/flowrate.go) tracks an
exponentially-weighted transfer rate and, via Limit(), tells callers how
many bytes they may move before sleeping.  MConnection wraps both
directions of every peer connection in one of these
(p2p/conn/connection.go:370,504).  Same semantics here, thread-safe.
"""

from __future__ import annotations

import threading
import time


class Monitor:
    """EWMA byte-rate monitor with a blocking token-bucket limiter."""

    def __init__(self, sample_period: float = 0.1, window: float = 1.0):
        self._lock = threading.Lock()
        self.sample_period = max(sample_period, 0.01)
        self.window = max(window, self.sample_period)
        self._weight = self.sample_period / self.window
        self.start = time.monotonic()
        self.total = 0  # total bytes transferred
        self._acc = 0  # bytes in the current sample
        self._sample_start = self.start
        self._rate = 0.0  # EWMA bytes/sec
        self._peak = 0.0  # highest single-sample rate seen
        self.samples = 0
        # token-bucket origin for limit(); kept separate from the stats
        # epoch `start` so credit-forfeiture can't corrupt avg_rate()
        self._limit_start = self.start
        self._limit_total = 0
        # seconds limit() has slept in all: the limiter's own share of a
        # slow transfer (its one caller reads it before and after)
        self.throttled_s = 0.0

    def update(self, n: int) -> int:
        """Record n bytes transferred; returns n."""
        with self._lock:
            self._tick_locked()
            self.total += n
            self._limit_total += n
            self._acc += n
        return n

    def _tick_locked(self):
        now = time.monotonic()
        elapsed = now - self._sample_start
        while elapsed >= self.sample_period:
            sample_rate = self._acc / self.sample_period
            if self.samples == 0:
                self._rate = sample_rate
            else:
                self._rate += self._weight * (sample_rate - self._rate)
            if sample_rate > self._peak:
                self._peak = sample_rate
            self.samples += 1
            self._acc = 0
            self._sample_start += self.sample_period
            elapsed -= self.sample_period

    def rate(self) -> float:
        """Current EWMA transfer rate, bytes/sec."""
        with self._lock:
            self._tick_locked()
            return self._rate

    def avg_rate(self) -> float:
        with self._lock:
            elapsed = time.monotonic() - self.start
            return self.total / elapsed if elapsed > 0 else 0.0

    def limit(self, want: int, rate_limit: int) -> int:
        """Block until at least some of `want` bytes may be transferred
        without exceeding rate_limit bytes/sec; returns the allowance
        (reference flowrate.Monitor.Limit semantics: callers loop).
        Idle credit is capped at one window's worth so a quiet
        connection can't bank an unbounded burst."""
        if rate_limit <= 0:
            return want
        while True:
            with self._lock:
                self._tick_locked()
                now = time.monotonic()
                elapsed = max(now - self._limit_start, 1e-9)
                allowed = rate_limit * elapsed - self._limit_total
                burst_cap = rate_limit * self.window
                if allowed > burst_cap:
                    # forfeit credit beyond one window by sliding the
                    # bucket origin forward
                    self._limit_start = now - (burst_cap + self._limit_total) / rate_limit
                    allowed = burst_cap
            if allowed >= 1:
                return min(want, int(allowed))
            t0 = time.monotonic()
            time.sleep(min((1 - allowed) / rate_limit, self.sample_period))
            self.throttled_s += time.monotonic() - t0

    def status(self) -> dict:
        with self._lock:
            self._tick_locked()
            elapsed = time.monotonic() - self.start
            return {
                "bytes": self.total,
                "duration": elapsed,
                "samples": self.samples,
                "cur_rate": self._rate,
                "avg_rate": self.total / elapsed if elapsed > 0 else 0.0,
                "peak_rate": self._peak,
            }
