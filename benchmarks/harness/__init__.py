"""The benchmark's yardstick: traffic, weights, the reduction from spans
and traces to metrics, the peaks table, FLOP and byte counts, and the
comparison that decides `correct`. What is about one model family lives
in `benchmarks/families/`. Later PRs change the program, not this."""
