"""``queue_wait_p95_ms`` in a cell that holds no bound on its first-token tail
(``first_token_p95_ms`` reports it per layer there): the same reader,
under the name whose ``moves`` such a cell reports (PERF.md section 2)."""

from perfbench.harness import loader

reduce = loader.twin_of(__file__, "queue_wait_p95_ms")
