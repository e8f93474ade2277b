"""On-chip benchmark of the served search path; see README.md."""
