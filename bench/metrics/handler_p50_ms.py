"""Transport: median time (ms) the HTTP handler took over a suggest
(parsing, the service, writing the response), from the program's
``http.request`` spans on route ``suggestions`` begun in the window.
Its distance from ``suggest_p50_ms`` is the generator, the socket and
the server's accept."""
from bench import spans


def read(run):
    return spans.quantile_ms(
        spans.durations(run, "http.request", route="suggestions"), 0.5)
