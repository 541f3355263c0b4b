"""The scope reduction under its former name.  It lives in
``bench/trace_reduce.py``, the one reduction of a trace;
``tests/test_tracing.py`` still imports it from here, and

    python bench/trace_scopes.py <file.xplane.pb> <step.hlo.txt>

still prints the reduction, as ``bench/trace_reduce.py`` does."""
from bench.trace_reduce import (MODULES, PHASES, hlo_scopes,  # noqa: F401
                                host_spans, read_events, reduce, scope_of)

if __name__ == "__main__":
    from bench.trace_reduce import main
    main()
