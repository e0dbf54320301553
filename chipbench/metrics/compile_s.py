"""compile_s: seconds of backend compilation in set-up (programs loaded
from the persistent cache count their load), from JAX's
backend_compile_duration events.  Moves setup_s."""


def read(rec):
    return rec.compile_s
