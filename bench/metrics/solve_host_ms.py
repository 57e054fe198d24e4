"""Host milliseconds per solve in the program's spans of work outside
the device: ``solver.fingerprint``, ``solver.initial_state`` and
``solver.unpermute``, read from the traced window on the host clock."""

from bench import scopes


def read(run):
    return scopes.solve_host_ms(run)
