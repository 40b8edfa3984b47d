"""The chip benchmark's own code: it reads cells, configurations, traffic
mixes and metric readers by name from the files beside it, drives the
served path, and reduces what it saw to metrics. Nothing here is imported
by the program under test."""
