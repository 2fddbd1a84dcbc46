"""The measured window keeps steps dispatched ahead, and every step it
sent finishes inside it."""
import time

import train_cell

STEP_S = 0.01


class Device:
    """A device that runs the steps sent to it one after another, STEP_S
    each, from when each is sent."""

    def __init__(self):
        self.free_at = 0.0
        self.in_flight = []
        self.most_in_flight = 0
        self.finished = 0

    def send(self):
        done_at = max(time.perf_counter(), self.free_at) + STEP_S
        self.free_at = done_at
        step = Pending(self, done_at)
        self.in_flight.append(step)
        self.most_in_flight = max(self.most_in_flight, len(self.in_flight))
        return step


class Pending:
    def __init__(self, device, done_at):
        self.device, self.done_at, self.waited = device, done_at, False

    def block_until_ready(self):
        """Wait for this step, and so for every step sent before it."""
        time.sleep(max(0.0, self.done_at - time.perf_counter()))
        for p in [p for p in self.device.in_flight
                  if p.done_at <= self.done_at]:
            p.waited = True
            self.device.in_flight.remove(p)
            self.device.finished += 1
        return self


class Trainer:
    def __init__(self, device, losses):
        self.device, self.losses = device, losses
        self.start_step, self.state = 0, None

    def run(self, num_steps, log_every, log_fn):
        for _ in range(self.start_step, num_steps):
            self.state = self.device.send()
            self.losses.append(self.state)


def test_steps_run_ahead_and_all_finish_inside(monkeypatch):
    monkeypatch.setattr(train_cell, "AHEAD_S", 10 * STEP_S)
    device, losses = Device(), []
    steps, seconds = train_cell.window(Trainer(device, losses), losses, 3,
                                       lambda n, t: t >= 0.5)
    assert steps == len(losses) == device.finished
    assert all(p.waited for p in losses)
    # about AHEAD_S of steps in flight, never one at a time
    assert 5 <= device.most_in_flight <= 15
    assert time.perf_counter() >= device.free_at
