"""The benchmark's yardstick: traffic, weights, the reduction from spans
and traces to metrics, the peaks table, FLOP and byte counts, and the
comparison that decides `correct`. Later PRs change the program, not
this."""
