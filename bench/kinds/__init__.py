"""Request kinds: what a request is, and everything that depends on it.

A traffic file names its kind under ``kind`` (:data:`DEFAULT` where it
names none), and the harness loads ``bench/kinds/<kind>.py`` by that name,
as it loads a per-layer metric's reader.  A kind module defines:

- ``data(config, seed)``: the cell's data, from the configuration and
  the run's seed;
- ``engine(config, data, obs)``: ``(engine, phases)``, the engine built
  from the configuration's ``engine`` settings, reporting through the
  harness's ``repro.obs.Obs``, and the seconds of its build phases.  The
  engine serves in the background between ``start()`` and ``stop()``;
- ``pool(traffic, data)``: the traffic's distinct requests, each
  hashable, in the order a window asks them;
- ``warm(engine, pool, tiers)``: builds every program that requests of
  the pool can run at each batch tier in ``tiers``, and returns a line
  that says what it warmed;
- ``submit(engine, request, arrival_at)``: admits one request due at
  ``arrival_at`` (``time.perf_counter`` seconds, or None) and returns the
  program's ``repro.serve.admission.Ticket``;
- ``answer(value)`` and ``route(value)``: the answer that a resolved
  ticket's value carries, as sorted unsigned integers, and the path that
  answered it;
- ``reference(data, request)``: the plain reference's answer, from the
  benchmark's own data, importing nothing of the program;
- ``control(data, request)``: the reference with the configuration's
  guarantee broken in the way a faster engine would be tempted to.

The harness keeps the rest: the log, the timed window, the comparison
that decides ``correct``, the profiler, the readers and the result line.
"""

DEFAULT = "conj"
