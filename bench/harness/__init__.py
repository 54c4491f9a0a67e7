"""The chip benchmark's harness: everything but the system under test."""
