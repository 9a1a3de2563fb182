"""The one failure every piece of the harness raises when a run cannot give
a result: run.py's main() logs it and exits non-zero without a result line.
A file of its own so that run.py (which is ``__main__`` when it is the
command) and the modules it imports name the same class."""


class BenchFailure(RuntimeError):
    """The run cannot give a result; main() exits non-zero without one."""
