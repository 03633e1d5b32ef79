"""The one in-process mock S3 of a test process.

The native S3 filesystem is a singleton that reads ``S3_ENDPOINT`` and the
credentials once, at its first use. A pytest worker imports every test file
it collects into one process, so two files that each started a server and
set the environment would leave one of them talking to the other's: every
test file that reads ``s3://`` in-process takes its server from here (a
plain module, imported once whatever name the test files go by). Objects
are ``STATE.objects[(bucket, key)] = bytes``; use a bucket of your own.
"""

import os

import tests.mock_s3 as mock_s3

STATE, PORT, SHUTDOWN = mock_s3.serve()
# before the native S3 singleton initializes
os.environ["S3_ENDPOINT"] = f"http://127.0.0.1:{PORT}"
os.environ["S3_ACCESS_KEY_ID"] = mock_s3.ACCESS_KEY
os.environ["S3_SECRET_ACCESS_KEY"] = mock_s3.SECRET_KEY
os.environ["S3_REGION"] = mock_s3.REGION
