"""Device idle milliseconds per traced job that fall inside the call of
``run_gpic``, the chip's events moved onto the host's clock
(``spans.clock_offset``, printed on stderr); on several chips, the idlest.
The rest of a job's idle time is the harness's own readback between
calls."""
from chipbench import spans


def read(run):
    return spans.idle_in_program_ms(run)
