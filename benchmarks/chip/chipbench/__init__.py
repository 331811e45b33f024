"""The chip benchmark's harness: traffic, corpus, engine, trace reduction, checks."""
