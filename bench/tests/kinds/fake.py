"""A request kind that no cell uses, for the test that a new kind enters
the benchmark as files alone.

A request is an index into a table of numbers drawn from the seed; its
answer is the entry doubled.  The engine is one worker thread that answers
through the program's ``Ticket``, adding the configuration's ``skew`` to
every answer, so a configuration with a skew serves wrong answers.
"""
import queue
import threading

import numpy as np


class Doubler:
    def __init__(self, table, skew):
        self.table, self.skew = table, skew
        self.q = None

    def start(self):
        self.q = queue.Queue()
        self.worker = threading.Thread(target=self._work, daemon=True)
        self.worker.start()

    def stop(self):
        self.q.put(None)
        self.worker.join(10)

    def submit(self, i, arrival_at=None):
        from repro.serve.admission import Ticket

        t = Ticket(submitted_at=arrival_at, deadline_us=0.0)
        self.q.put((t, i))
        return t

    def _work(self):
        while (item := self.q.get()) is not None:
            t, i = item
            t.resolve(2 * int(self.table[i]) + self.skew)


def data(config, seed):
    return np.random.default_rng(seed).integers(0, 1 << 20, config["size"])


def engine(config, table, obs):
    return Doubler(table, config["engine"]["skew"]), {}


def pool(traffic, table):
    return list(range(min(traffic["distinct_queries"], len(table))))


def warm(eng, pool, tiers):
    return "nothing to compile"


def submit(eng, i, arrival_at):
    return eng.submit(i, arrival_at=arrival_at)


def answer(value):
    return np.array([value], np.uint32)


def route(value):
    return "fake"


def reference(table, i):
    return np.array([2 * int(table[i])], np.uint32)


def control(table, i):
    return np.array([2 * int(table[i]) + 1], np.uint32)
