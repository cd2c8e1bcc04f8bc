"""Drivers: what runs a cell, named by its traffic file's ``"driver"``.

``drivers/<name>.py`` provides:

- ``SPAN``: the prefix of the host annotation around each timed call
  (``<SPAN><i>``, ``i`` the call's index in the run), and ``IDLE``: the
  labels of device idle time inside such a call and outside every call.
- ``drive(cell, traffic, seconds, *, rehearse, t_process, trace_dir,
  patch, meter) -> window``: warm up, measure one window of ``seconds``,
  stop.  ``patch`` (tests and the control only) breaks the timed path
  underneath before anything runs.  The window has ``first`` and
  ``last`` (the calls whose ends open and close it, or None),
  ``setup_s``, ``phases`` (set-up milestones), ``compiles_in_window``
  and ``stalls``.
- ``compare(window, seed, devices, traffic) -> (checks, attempted,
  failed)``: the comparison with the plain reference that decides
  ``correct``; ``checks`` maps each number's name to ``{"value",
  "limit"}``.
- ``view(window, cell, reduced, peak)``: what the metric readers of the
  cell are handed; it has ``setup_s`` and, traced, the reduced trace.
- ``facts(window) -> dict``: the driver's own facts for the result line.
- ``control(target)``: the control of ``perfbench/control.py``, a
  ``patch`` that the comparison must call not correct.
"""
